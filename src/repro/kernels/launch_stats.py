"""Trace-time kernel-launch accounting, shared by all kernel families.

Each python-level kernel-wrapper call is one ``pallas_call`` site in
the traced program (vmap/grid batching does not multiply it), so
benchmarks measure launches-per-sync-round by resetting, tracing, and
reading.  Kept in its own module so kernel families don't import each
other just to count.
"""

from __future__ import annotations

from collections import Counter

LAUNCHES = {"topk_compress": 0, "topk_compact": 0, "qsgd": 0,
            "sparse_gemm": 0, "qdq_gemm": 0, "flash_decode": 0,
            "paged_decode": 0}

#: serving page-pool gauges, refreshed by ``ServeEngine.step()`` when
#: the paged KV runtime is active (DESIGN.md §12): pages used/free and
#: peak, internal fragmentation (1 - live_tokens / (used_pages *
#: page_size)), preemptions (recompute-from-start evictions) and
#: admission stalls (queue head blocked on pages, not slots).
PAGE_POOL = {"pages_used": 0, "pages_free": 0, "peak_pages_used": 0,
             "fragmentation": 0.0, "preemptions": 0, "admission_stalls": 0}

#: trace-time tuning-table resolution counters (kernels/autotune.py):
#: ``hit`` — the LRU already held the shape's resolution, ``miss`` — the
#: persisted table (or the heuristic fallback) had to be consulted.
#: Incremented only when a DispatchConfig leaves ``block_rows`` on auto.
TUNE_CACHE = {"hit": 0, "miss": 0}


#: trace-time block heights of ``topk_compress`` launches:
#: ``(kernel, how, block_rows)`` -> launches, where ``how`` says what set
#: the height — ``explicit`` (``DispatchConfig.block_rows``), ``table``
#: (the autotune table) or ``rule`` (``topk_compress.dense_block_rows``
#: of the rows the kernel tiles).  Cleared with the launch counts.
BLOCK_ROWS: Counter = Counter()


def reset_launches() -> None:
    for k in LAUNCHES:
        LAUNCHES[k] = 0
    BLOCK_ROWS.clear()


def reset_tune_cache() -> None:
    for k in TUNE_CACHE:
        TUNE_CACHE[k] = 0


def reset_page_pool() -> None:
    for k in PAGE_POOL:
        PAGE_POOL[k] = 0.0 if k == "fragmentation" else 0


def total_launches() -> int:
    return sum(LAUNCHES.values())
