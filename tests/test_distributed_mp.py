"""Multi-device matrix-profile tests — run in a subprocess with 8 forced
host devices so the main pytest process keeps its single CPU device."""

import json
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_SNIPPET = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, %r)
import numpy as np, jax, jax.numpy as jnp
from repro.core.scheduler import AnytimeScheduler
from repro.core.ref import matrix_profile_bruteforce

mesh = jax.make_mesh((8,), ("workers",), (jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(1)
ts = np.cumsum(rng.normal(size=600)).astype(np.float32)
m = 20
p_ref, _ = matrix_profile_bruteforce(jnp.asarray(ts), m, exclusion=5)
out = {}

sch = AnytimeScheduler(ts, m, mesh, chunks_per_worker=4, band=16)
prev = None
mono = True
for r in range(sch.plan.n_rounds):
    st = sch.step_round()
    d = np.asarray(st.profile.to_distance(m))
    if prev is not None and not (d <= prev + 1e-5).all():
        mono = False
    prev = d
p = sch.distance_profile().p   # fused rounds: run() alone is exact
out["monotone"] = mono
out["err"] = float(np.abs(np.asarray(p) - np.asarray(p_ref)).max())

# failure + elastic resume
sch2 = AnytimeScheduler(ts, m, mesh, chunks_per_worker=4, band=16)
sch2.step_round(); sch2.step_round(fail_workers={3})
sch2.checkpoint("/tmp/mp_test_ckpt.npz")
sch3 = AnytimeScheduler(ts, m, mesh, chunks_per_worker=4, band=16)
sch3.resume("/tmp/mp_test_ckpt.npz", n_workers=5)   # elastic shrink
sch3.run()
p3 = sch3.distance_profile().p
out["err_resume"] = float(np.abs(np.asarray(p3) - np.asarray(p_ref)).max())
out["frac_after_fail"] = sch2.state.fraction_done

# multi-round failures + a resume CHAIN (shrink then grow back), finishing
# BITWISE equal to the clean run: chunk contributions are plan-invariant
# and the f32 max-merge commutes in value
r_clean = sch.distance_profile()
p_clean, i_clean = np.asarray(r_clean.p), np.asarray(r_clean.i)
s4 = AnytimeScheduler(ts, m, mesh, chunks_per_worker=4, band=16)
s4.step_round(fail_workers={1, 5})
s4.step_round(fail_workers={1})            # same worker fails again
s4.step_round(fail_workers={0, 2, 7})
s4.checkpoint("/tmp/mp_test_chain1.npz")
s5 = AnytimeScheduler(ts, m, mesh, chunks_per_worker=4, band=16)
s5.resume("/tmp/mp_test_chain1.npz", n_workers=3)   # shrink to 3
s5.step_round(); s5.step_round(fail_workers={2})
s5.checkpoint("/tmp/mp_test_chain2.npz")
s6 = AnytimeScheduler(ts, m, mesh, chunks_per_worker=4, band=16)
s6.resume("/tmp/mp_test_chain2.npz", n_workers=8)   # grow back to 8
s6.run()                                            # resume-after-resume
r6 = s6.distance_profile()
out["chain_bitwise_p"] = bool(np.array_equal(np.asarray(r6.p), p_clean))
out["chain_bitwise_i"] = bool(np.array_equal(np.asarray(r6.i), i_clean))
out["chain_frac_mid"] = s5.state.fraction_done

# AB join across the same 8-worker mesh (signed rectangular plan)
from repro.core.ref import ab_join_bruteforce
ts_b = np.cumsum(rng.normal(size=250)).astype(np.float32)
pab_ref, _ = ab_join_bruteforce(jnp.asarray(ts), jnp.asarray(ts_b), m)
ab = AnytimeScheduler(ts, m, mesh, ts_b=ts_b, chunks_per_worker=4, band=16)
prev = None
ab_mono = True
for r in range(ab.plan.n_rounds):
    st = ab.step_round()
    d = np.asarray(st.profile.to_distance(m))
    if prev is not None and not (d <= prev + 1e-5).all():
        ab_mono = False
    prev = d
pab = ab.distance_profile().p
out["ab_monotone"] = ab_mono
out["ab_err"] = float(np.abs(np.asarray(pab) - np.asarray(pab_ref)).max())

# top-k across REAL multi-worker rounds: the union all-reduce must stay an
# exact top-k when every round's gather carries 8 workers' candidate sets
# (a 1-worker mesh cannot exercise the duplicate-eviction failure mode —
# the running state must be merged once, not once per worker)
from repro.core.ref import distance_matrix
k = 4
excl = 5
dm = np.array(distance_matrix(jnp.asarray(ts), m))
ii = np.arange(dm.shape[0])
dm[np.abs(ii[:, None] - ii[None, :]) < excl] = np.inf
ref_topk = np.sort(np.partition(dm, k - 1, axis=1)[:, :k], axis=1)
sk = AnytimeScheduler(ts, m, mesh, chunks_per_worker=4, band=16,
                      exclusion=excl, k=k)
sk.run()
rk = sk.result()
out["topk_err"] = float(np.abs(np.asarray(rk.topk_p) - ref_topk).max())
dup = 0
tki = np.asarray(rk.topk_i)
for row in tki:
    live = row[row >= 0]
    dup = max(dup, len(live) - len(set(live.tolist())))
out["topk_dup"] = dup
print(json.dumps(out))
""" % (SRC,)


@pytest.fixture(scope="module")
def results():
    proc = subprocess.run([sys.executable, "-c", _SNIPPET],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_multiworker_exact(results):
    assert results["err"] < 2e-3


def test_anytime_monotone_across_workers(results):
    assert results["monotone"]


def test_failure_and_elastic_resume_exact(results):
    assert results["err_resume"] < 2e-3
    assert 0.0 < results["frac_after_fail"] < 1.0


def test_multi_round_failures_and_resume_chain_bitwise(results):
    """Consecutive-round worker failures, shrink-to-3 resume, then a
    grow-to-8 resume-after-resume must finish BITWISE equal to the clean
    run — not merely close."""
    assert results["chain_bitwise_p"]
    assert results["chain_bitwise_i"]
    assert 0.0 < results["chain_frac_mid"] < 1.0


def test_ab_join_multiworker_exact_and_monotone(results):
    assert results["ab_err"] < 2e-3
    assert results["ab_monotone"]


def test_topk_multiworker_exact_no_duplicates(results):
    """8-worker top-k schedule == np.partition oracle, and no position's
    neighbour set contains duplicates (the symptom of all-reducing an
    already-merged running state)."""
    assert results["topk_err"] < 2e-3
    assert results["topk_dup"] == 0
