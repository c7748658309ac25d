"""Dry-run plumbing test: the exact lowering/compile/analysis path used
for the production matrix, on reduced configs + an 8-device mesh (full
configs x 256/512 devices run via `python -m repro.launch.dryrun`)."""

import pytest

pytestmark = pytest.mark.slow

CODE = r"""
from repro.launch import dryrun as dr
from repro.launch.mesh import make_mesh
from repro.configs.base import InputShape

mesh = make_mesh((4, 2), ("data", "model"))
shapes = {
    "train_4k": InputShape("train_4k", 64, 8, "train"),
    "prefill_32k": InputShape("prefill_32k", 64, 4, "prefill"),
    "decode_32k": InputShape("decode_32k", 64, 8, "decode"),
    "long_500k": InputShape("long_500k", 128, 1, "decode"),
}
combos = [
    ("yi-6b", "train_4k"), ("yi-6b", "decode_32k"),
    ("gemma3-1b", "long_500k"),
    ("qwen3-moe-30b-a3b", "train_4k"),
    ("rwkv6-3b", "long_500k"),
    ("zamba2-7b", "train_4k"),
    ("musicgen-medium", "prefill_32k"),
    ("yi-34b", "long_500k"),          # must be skipped
]
for arch, shp in combos:
    rec = dr.run_one(arch, shp, smoke=True, mesh=mesh,
                     shape_override=shapes[shp])
    expect_skip = (arch == "yi-34b" and shp == "long_500k")
    if expect_skip:
        assert rec["status"] == "skipped", rec
        continue
    assert rec["status"] == "ok", (arch, shp, rec.get("error"))
    step = next(iter(rec["steps"].values()))
    assert step["flops"] > 0
    assert step["memory"]["temp_bytes"] >= 0
    assert "collectives" in step
print("DRYRUN SMOKE OK")
"""


def test_dryrun_smoke_path(subproc):
    out = subproc(CODE, devices=8, timeout=1500)
    assert "DRYRUN SMOKE OK" in out


CODE_SPARSE = r"""
from repro.launch import dryrun as dr
from repro.launch.mesh import make_mesh
from repro.configs.base import InputShape

mesh = make_mesh((8, 1), ("data", "model"))
shp = InputShape("train_4k", 64, 8, "train")
rec = dr.run_one("yi-6b", "train_4k", smoke=True, mesh=mesh,
                 shape_override=shp, aggregate="sparse_allgather")
assert rec["status"] == "ok", rec.get("error")
assert rec["aggregate"] == "sparse_allgather"
sync = rec["steps"]["sync_step"]
assert sync["flops"] > 0
assert "collectives" in sync
print("DRYRUN SPARSE OK")
"""


def test_dryrun_sparse_allgather(subproc):
    """The sparse-allgather train compile: the compact wire path traces
    without lax.top_k."""
    out = subproc(CODE_SPARSE, devices=8, timeout=1500)
    assert "DRYRUN SPARSE OK" in out


def test_collective_parser():
    from repro.launch import roofline_parse
    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(bf16[1,128]{1,0} %x), dimensions={0}
  %ar = f32[256]{0} all-reduce(f32[256]{0} %y), to_apply=%sum
  %rs = f32[2,4]{1,0} reduce-scatter(f32[16,4]{1,0} %z), dimensions={0}
  %a2a = bf16[4,16]{1,0} all-to-all(bf16[4,16]{1,0} %w), dimensions={0}
  %cp = f32[10]{0} collective-permute(f32[10]{0} %v), source_target_pairs={{0,1}}
"""
    out = roofline_parse.collective_bytes(hlo)
    assert out["all-gather"] == 8 * 128 * 2
    assert out["all-reduce"] == 256 * 4
    assert out["reduce-scatter"] == 2 * 4 * 4
    assert out["all-to-all"] == 4 * 16 * 2
    assert out["collective-permute"] == 40
    assert out["total"] == sum(v for k, v in out.items() if k != "total")
