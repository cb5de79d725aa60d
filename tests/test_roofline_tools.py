"""Unit tests for the roofline HLO parser, sharding sanitizer, flops model,
and the non-normalized matrix-profile mode used by the telemetry monitor."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch import roofline
from repro.models.common import sanitize_pspec
from repro.utils import flops as F
from repro import configs
from repro.configs.base import SHAPES


# -- HLO parsing --------------------------------------------------------------


def test_shape_bytes():
    assert roofline.shape_bytes("bf16[2048,4096]") == 2048 * 4096 * 2
    assert roofline.shape_bytes("f32[8]") == 32
    assert roofline.shape_bytes("(f32[4,4], bf16[2,2])") == 64 + 8
    assert roofline.shape_bytes("pred[16]") == 16


HLO_SAMPLE = """
  %ar = f32[128,256]{1,0} all-reduce(%x), replica_groups=[32,16]<=[512], to_apply=%sum
  %ag.1 = bf16[64,512]{1,0} all-gather(%y), replica_groups={{0,1,2,3}}, dimensions={1}
  %rs = f32[32]{0} reduce-scatter(%z), replica_groups=[16,2]<=[32]
  %cp = bf16[8,8]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
  %a2a = f32[16,16]{1,0} all-to-all(%v), replica_groups=[2,8]<=[16]
  %done = f32[4] all-reduce-done(%ar)
"""


def test_parse_collectives_kinds_and_groups():
    cs = roofline.parse_collectives(HLO_SAMPLE, default_group=16)
    kinds = sorted(c.kind for c in cs)
    assert kinds == ["all-gather", "all-reduce", "all-to-all",
                     "collective-permute", "reduce-scatter"]
    ar = next(c for c in cs if c.kind == "all-reduce")
    assert ar.group == 16 and ar.result_bytes == 128 * 256 * 4
    ag = next(c for c in cs if c.kind == "all-gather")
    assert ag.group == 4
    # ring costs
    assert ar.wire_bytes == pytest.approx(2 * ar.result_bytes * 15 / 16)
    assert ag.wire_bytes == pytest.approx(ag.result_bytes * 3 / 4)


def test_roofline_terms_bottleneck():
    t = roofline.RooflineTerms(flops_per_chip=197e12, bytes_per_chip=0,
                               wire_bytes_per_chip=0, model_flops_total=197e12,
                               n_chips=1)
    assert t.bottleneck == "compute" and t.t_compute == pytest.approx(1.0)
    t2 = roofline.RooflineTerms(flops_per_chip=0, bytes_per_chip=819e9,
                                wire_bytes_per_chip=10e9,
                                model_flops_total=1.0, n_chips=1)
    assert t2.bottleneck == "memory"      # 1.0 s vs 0.2 s collective
    t3 = roofline.RooflineTerms(flops_per_chip=0, bytes_per_chip=0,
                                wire_bytes_per_chip=100e9,
                                model_flops_total=1.0, n_chips=1)
    assert t3.bottleneck == "collective"


# -- sanitizer ---------------------------------------------------------------


def test_sanitize_pspec_rules():
    mesh = jax.make_mesh((1,), ("model",), (jax.sharding.AxisType.Auto,))

    class FakeMesh:
        shape = {"model": 16, "data": 4}
    fm = FakeMesh()
    # non-divisible -> dropped
    assert sanitize_pspec((40, 64), P("model", None), fm) == P(None, None)
    # divisible -> kept
    assert sanitize_pspec((64, 32), P("model", None), fm) == P("model", None)
    # duplicate axis -> first wins
    assert sanitize_pspec((64, 64), P("model", "model"), fm) == P("model", None)
    # tuple axes
    assert sanitize_pspec((64,), P(("data", "model")), fm) == P(("data", "model"))
    assert sanitize_pspec((40,), P(("data", "model")), fm) == P(None)
    del mesh


# -- analytic flops -----------------------------------------------------------


@pytest.mark.parametrize("arch", ["llama3-8b", "olmoe-1b-7b", "rwkv6-3b"])
def test_model_flops_sane(arch):
    cfg = configs.get_config(arch)
    pc = F.param_counts(cfg)
    assert 0 < pc["active"] <= pc["total"]
    tr = F.model_flops(cfg, SHAPES["train_4k"])
    de = F.model_flops(cfg, SHAPES["decode_32k"])
    assert tr["total"] > de["total"] > 0
    # train is ~3x prefill at same tokens per the fwd/bwd multiplier
    pf = F.model_flops(cfg, SHAPES["prefill_32k"])
    tokens_ratio = tr["tokens"] / pf["tokens"]
    assert tr["dense"] / pf["dense"] == pytest.approx(3 * tokens_ratio)


def test_moe_active_excludes_inactive_experts():
    cfg = configs.get_config("olmoe-1b-7b")
    pc = F.param_counts(cfg)
    # 64 experts, top-8: active ffn ~= total ffn / 8
    assert pc["active"] < pc["total"] * 0.35


def test_kernel_roofline_regimes():
    from repro.kernels import ops
    small = ops.kernel_roofline(131072, 64, 512, 32)
    big = ops.kernel_roofline(2097152, 64, 512, 32)
    assert small["resident"] and not big["resident"]
    assert small["bytes_per_cell"] < 0.01 < big["bytes_per_cell"]
    assert small["t_compute_s"] > small["t_memory_s"]      # compute-bound
    # tile hillclimb direction
    worse = ops.kernel_roofline(2097152, 64, 256, 8)
    assert big["bytes_per_cell"] < worse["bytes_per_cell"]


def test_matrix_profile_roofline_bridges_kernel_model():
    """matrix_profile_roofline == kernel_roofline's terms, expressed as
    RooflineTerms (ROADMAP item 2: bytes_per_cell wired into the shared
    roofline vocabulary)."""
    from repro.kernels import DEFAULT_DT, DEFAULT_IT, ops

    l, excl = 131072, 64
    t = roofline.matrix_profile_roofline(l, excl, it=512, dt=32)
    ref = ops.kernel_roofline(l, excl, 512, 32)
    assert t.t_compute == pytest.approx(ref["t_compute_s"])
    assert t.t_memory == pytest.approx(ref["t_memory_s"])
    assert t.wire_bytes_per_chip == 0 and t.t_collective == 0
    # defaults come from the SHARED kernel constants, not local copies
    t_def = roofline.matrix_profile_roofline(l, excl)
    ref_def = ops.kernel_roofline(l, excl, DEFAULT_IT, DEFAULT_DT)
    assert t_def.t_memory == pytest.approx(ref_def["t_memory_s"])
    # regime verdicts: VMEM-resident sweep is compute-bound; the streamed
    # regime past residency flips memory-bound (the NATSA motivation)
    small = roofline.matrix_profile_roofline(16384, 64)
    assert small.bottleneck == "compute"
    big = roofline.matrix_profile_roofline(2097152, 64, it=512, dt=32)
    assert big.bottleneck == "memory"
    assert big.step_time == pytest.approx(big.t_memory)


# -- non-normalized profile (telemetry mode) ----------------------------------


def test_nonnorm_profile_matches_bruteforce():
    from repro.core.matrix_profile import matrix_profile
    rng = np.random.default_rng(3)
    ts = rng.normal(size=300).astype(np.float32)
    m, excl = 16, 4
    p = matrix_profile(jnp.asarray(ts), m, excl, normalize=False).p
    l = 300 - m + 1
    w = np.stack([ts[i:i + m] for i in range(l)])
    d = np.sqrt(((w[:, None] - w[None, :]) ** 2).sum(-1))
    ii = np.arange(l)
    d[np.abs(ii[:, None] - ii[None, :]) < excl] = np.inf
    np.testing.assert_allclose(np.asarray(p), d.min(1), rtol=1e-3, atol=1e-3)


def test_nonnorm_detects_level_anomaly():
    from repro.core.matrix_profile import matrix_profile
    rng = np.random.default_rng(0)
    ts = (2.0 + 0.01 * rng.normal(size=400)).astype(np.float32)
    ts[250:266] += np.linspace(0, 1.0, 16).astype(np.float32)
    p = np.asarray(matrix_profile(jnp.asarray(ts), 16, 4,
                                  normalize=False).p)
    assert 235 <= int(np.argmax(np.where(np.isfinite(p), p, -1))) <= 266
