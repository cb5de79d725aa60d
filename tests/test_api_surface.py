"""API-surface snapshot: the public `repro.core` namespace and the
`ProfileResult` / `HarvestSpec` / `SweepPlan` field lists are PINNED.

A failing test here means the public API changed. That is sometimes the
point — then update the snapshot IN THE SAME change and say so in the PR —
but it must never happen as a side effect. CI runs this with the
plan-dispatch job on both supported jax versions, so an accidental rename,
a lost re-export, or a dataclass-field drift cannot slip through while the
behavioural suites still pass.
"""

import dataclasses

import repro.core as core
from repro.core.plan import SweepPlan
from repro.core.result import HarvestSpec, ProfileResult

CORE_ALL = [
    "CrossStats",
    "DEFAULT_PRECISION",
    "HarvestSpec",
    "PrecisionSpec",
    "ProfileResult",
    "ProfileState",
    "StreamingFleet",
    "SweepPlan",
    "SweepResult",
    "TopKState",
    "ZStats",
    "ab_join",
    "analytics",
    "as_precision",
    "batch_ab_join",
    "batch_profile",
    "compute_cross_stats_host",
    "compute_stats",
    "corr_to_dist",
    "execute",
    # matrix_profile_nonnorm: collapsed into matrix_profile(normalize=False)
    # in PR 8; its one-release forwarding shim retired this release
    # (checked below)
    "matrix_profile",
    "plan_sweep",
    "round_executor",
    "self_cross",
    "top_discords",
    "top_motif",
]

# ProfileResult is a plain frozen class since the lazy-harvest rework (the
# tuple shim and its `legacy_arity` field retired with it); the pinned
# surface is its CONSTRUCTOR — positional profile, keyword sides/meta —
# plus the lazy-field roster the descriptors expose.
PROFILE_RESULT_PARAMS = [
    "p",
    "i",
    "left_p",
    "left_i",
    "right_p",
    "right_i",
    "b_p",
    "b_i",
    "topk_p",
    "topk_i",
    "b_topk_p",
    "b_topk_i",
    "kind",
    "window",
    "exclusion",
    "normalize",
    "k",
    "backend",
    "fraction_done",
    "lazy",
]

PROFILE_RESULT_LAZY_FIELDS = [
    "left_p",
    "left_i",
    "right_p",
    "right_i",
    "b_p",
    "b_i",
    "topk_p",
    "topk_i",
    "b_topk_p",
    "b_topk_i",
]

HARVEST_SPEC_FIELDS = ["sides", "k"]

SWEEP_PLAN_FIELDS = [
    "kind",
    "l_a",
    "l_b",
    "window",
    "exclusion",
    "normalize",
    "harvest",
    "swap_ab",
    "band",
    "col_tile",
    "n_bands",
    "it",
    "dt",
    "reseed_every",
    "backend",
    "interpret",
    "batch",
    "precision",
]


def _fields(cls):
    return [f.name for f in dataclasses.fields(cls)]


def test_core_all_is_pinned():
    assert core.__all__ == CORE_ALL
    for name in CORE_ALL:
        assert hasattr(core, name), name


def test_nonnorm_shim_retired():
    """The one-release deprecation shim has served its release and is gone
    from BOTH old locations; matrix_profile(normalize=False) is the one
    nonnorm entry."""
    import numpy as np
    import pytest

    with pytest.raises(ImportError):
        from repro.core import matrix_profile_nonnorm  # noqa: F401
    with pytest.raises(ImportError):
        from repro.core.matrix_profile import (  # noqa: F401
            matrix_profile_nonnorm as shim2,
        )
    ts = np.sin(np.arange(128, dtype=np.float32) / 5.0)
    new = core.matrix_profile(ts, 16, normalize=False)
    assert not new.normalize


def test_precision_surface_is_pinned():
    """PrecisionSpec is plan-time state: frozen, hashable, string dtype
    fields, presets resolvable through as_precision."""
    from repro.core import DEFAULT_PRECISION, PrecisionSpec, as_precision

    assert _fields(PrecisionSpec) == ["stream", "accum", "seed_dot"]
    assert DEFAULT_PRECISION == PrecisionSpec()
    assert DEFAULT_PRECISION.is_default
    assert hash(DEFAULT_PRECISION) == hash(PrecisionSpec())
    for preset in ("f32", "default", "bf16", "f16", "f64"):
        spec = as_precision(preset)
        assert isinstance(spec, PrecisionSpec), preset
    assert as_precision(None) is DEFAULT_PRECISION
    assert as_precision("bf16").reduced_stream
    assert not as_precision("f32").reduced_stream


def test_profile_result_surface_is_pinned():
    import inspect

    params = [p for p in inspect.signature(ProfileResult.__init__).parameters
              if p != "self"]
    assert params == PROFILE_RESULT_PARAMS
    assert list(ProfileResult.LAZY_FIELDS) == PROFILE_RESULT_LAZY_FIELDS
    for name in PROFILE_RESULT_LAZY_FIELDS:
        assert isinstance(getattr(ProfileResult, name), property), name
    # the retired tuple shim must stay retired
    for dunder in ("__iter__", "__getitem__", "__len__"):
        assert not hasattr(ProfileResult, dunder), dunder
    assert not hasattr(ProfileResult, "legacy_arity")


def test_harvest_spec_fields_are_pinned():
    assert _fields(HarvestSpec) == HARVEST_SPEC_FIELDS


def test_sweep_plan_fields_are_pinned():
    assert _fields(SweepPlan) == SWEEP_PLAN_FIELDS


def test_analytics_surface():
    from repro.core import analytics

    for name in ("top_motifs", "discords", "top_discord", "regimes",
                 "corrected_arc_curve", "Motif", "Discord", "Regimes"):
        assert hasattr(analytics, name), name


def test_entry_points_return_profile_result():
    """The v2 contract itself: every core entry point's return type."""
    import inspect

    assert "ProfileResult" in (inspect.signature(core.matrix_profile)
                               .return_annotation)
    assert "ProfileResult" in inspect.signature(core.ab_join).return_annotation
