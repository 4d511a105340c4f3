"""Roofline machinery: HLO shape parsing, collective cost model, and the
loop-aware analyzer validated against a known computation (run in a
subprocess so the 8-device XLA flag doesn't leak into this process)."""
import json
import subprocess
import sys
import textwrap

import pytest

from repro.models.config import ArchConfig
from repro.roofline import analyze, model_flops, shape_bytes
from repro.roofline.model import RooflineReport


def test_shape_bytes():
    assert shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert shape_bytes("bf16[2,3]") == 12
    assert shape_bytes("pred[]") == 1
    assert shape_bytes("(f32[4], s32[2])") == 24
    assert shape_bytes("token[]") == 0


def test_collective_cost_model_on_synthetic_hlo():
    hlo = textwrap.dedent("""\
    HloModule test, entry_computation_layout={()->f32[]}

    ENTRY %main (p: f32[1024]) -> f32[] {
      %p = f32[1024]{0} parameter(0)
      %ar = f32[1024]{0} all-reduce(%p), replica_groups=[2,8]<=[16], to_apply=%add
      %ag = f32[4096]{0} all-gather(%p), replica_groups=[4,4]<=[16], dimensions={0}
      %cp = f32[1024]{0} collective-permute(%p), source_target_pairs={{0,1}}
      ROOT %r = f32[] constant(0)
    }
    """)
    st = analyze(hlo, 16)
    b = 1024 * 4
    assert st.collective_by_kind["all-reduce"] == pytest.approx(
        2 * (7 / 8) * b)
    assert st.collective_by_kind["all-gather"] == pytest.approx(
        (3 / 4) * 4096 * 4)
    assert st.collective_by_kind["collective-permute"] == pytest.approx(b)


def test_analyzer_loop_and_flops_subprocess():
    code = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.roofline import analyze
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    D, L = 128, 7
    def f(x, w):
        def body(h, wi):
            return jnp.tanh(h @ wi), None
        h, _ = jax.lax.scan(body, x, w)
        return h.sum()
    xs = NamedSharding(mesh, P("data", None))
    ws = NamedSharding(mesh, P(None, None, "model"))
    comp = jax.jit(f, in_shardings=(xs, ws)).lower(
        jax.ShapeDtypeStruct((64, D), jnp.float32),
        jax.ShapeDtypeStruct((L, D, D), jnp.float32)).compile()
    st = analyze(comp.as_text(), 8)
    print(json.dumps({"flops": st.flops, "trips": st.while_trips,
                      "hbm": st.hbm_bytes,
                      "coll": st.collective_by_kind}))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # per-device: 32 rows x 128 contract x 32 cols... sharded: rows 64/2,
    # cols 128/4, times L layers
    expected = 2 * 32 * 128 * 32 * 7
    assert res["flops"] == pytest.approx(expected, rel=0.01)
    assert 7 in res["trips"]
    assert res["hbm"] > 0
    assert res["coll"].get("all-gather", 0) > 0


def test_model_flops_scaling():
    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                     vocab=100)
    t = model_flops(cfg, "train", batch=4, seq=32)
    p = model_flops(cfg, "prefill", batch=4, seq=32)
    d = model_flops(cfg, "decode", batch=4, seq=32)
    assert t > p > d > 0
    assert t / p == pytest.approx(3.0, rel=0.01)   # bwd = 2x fwd
    t2 = model_flops(cfg, "train", batch=8, seq=32)
    assert t2 == pytest.approx(2 * t, rel=0.01)


def test_roofline_report_bottleneck():
    rep = RooflineReport(
        arch="a", shape="s", mesh="16x16", chips=256,
        flops_per_device=197e12,          # exactly 1s of compute
        bytes_per_device=819e9 / 2,       # 0.5s of memory
        collective_bytes_per_device=50e9 * 2,   # 2s of collectives
        collective_by_kind={}, model_flops_global=197e12 * 256,
    ).finalize()
    assert rep.bottleneck == "collective"
    assert rep.compute_s == pytest.approx(1.0)
    assert rep.memory_s == pytest.approx(0.5)
    assert rep.collective_s == pytest.approx(2.0)
    assert rep.useful_ratio == pytest.approx(1.0)
    assert rep.peak_fraction == pytest.approx(0.5)
