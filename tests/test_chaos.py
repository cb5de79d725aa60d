"""Chaos harness: the supervised anytime loop must converge to a profile
BITWISE-equal to an uninterrupted run under every injected fault schedule —
worker crashes each round, transient round failures with retries,
kill-mid-checkpoint writes, corrupted-checkpoint restores, and shrinking to
a single surviving worker.

Why bitwise equality is even attainable: a chunk's contribution to the
merged profile is a pure function of the chunk bounds (independent of the
round it runs in or the n_bands padding — fully-masked scan bands merge as
no-ops), and the f32 max-merge is commutative in value, so any fault-and-
replan history that eventually commits every chunk exactly reproduces the
clean run's values. Runs in a subprocess with 8 forced host devices, same
idiom as test_distributed_mp.py.
"""

import json
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_SNIPPET = r"""
import os, json, tempfile, warnings
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, %r)
import numpy as np, jax
from repro.core.scheduler import AnytimeScheduler
from repro.core.faults import FaultInjector, FaultPolicy, flip_bits

mesh = jax.make_mesh((8,), ("workers",), (jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(3)
ts = np.cumsum(rng.normal(size=700)).astype(np.float32)
m = 24
nosleep = lambda s: None
mk = lambda: AnytimeScheduler(ts, m, mesh, chunks_per_worker=4, band=16)
td = tempfile.mkdtemp()

clean = mk()
clean.run()
rc = clean.result()
pc, ic = np.asarray(rc.p), np.asarray(rc.i)
out = {}

def check(name, res):
    out[name + "_p"] = bool(np.array_equal(np.asarray(res.p), pc))
    out[name + "_i"] = bool(np.array_equal(np.asarray(res.i), ic))
    out[name + "_frac"] = res.fraction_done

# 1. a worker crashes EVERY round (rotating slot), no exclusion: every
#    crashed chunk must be replanned and the final answer stay bitwise
s = mk()
inj = FaultInjector(worker_crashes={t: {t %% 8} for t in range(64)})
res = s.run_supervised(FaultPolicy(sleep=nosleep,
                                   worker_failure_threshold=100),
                       injector=inj)
check("crash_every_round", res)
out["crash_rounds"] = s.supervised_report.rounds
out["crash_replans"] = s.supervised_report.replans

# 2. transient round failures, retried with (zero-cost) backoff
s = mk()
inj = FaultInjector(round_failures={0: 1, 2: 3, 5: 2})
res = s.run_supervised(FaultPolicy(sleep=nosleep), injector=inj)
check("transient", res)
out["retries"] = s.supervised_report.retries

# 3. checkpoint-every-round with a kill-mid-write and a bit-flip scheduled;
#    the run itself must be undisturbed (checkpointing is off the hot path)
ck = os.path.join(td, "chaos.npz")
s = mk()
inj = FaultInjector(checkpoint_kills={1}, checkpoint_flips={3}, seed=7)
res = s.run_supervised(FaultPolicy(sleep=nosleep, checkpoint_every=1),
                       checkpoint_path=ck, injector=inj)
check("ckpt_chaos", res)
rep = s.supervised_report
out["ckpt_failures"] = rep.checkpoint_failures
out["ckpt_corrupted"] = rep.checkpoints_corrupted
out["ckpt_written"] = rep.checkpoints_written

# 4. corrupted-latest restore: interrupt a checkpointing run halfway,
#    corrupt the newest checkpoint on disk, resume a FRESH scheduler from
#    it (falls back to .prev), supervise to completion -> bitwise
ck2 = os.path.join(td, "resume.npz")
s = mk()
s.run_supervised(FaultPolicy(sleep=nosleep, checkpoint_every=1),
                 checkpoint_path=ck2, max_rounds=3)
flip_bits(ck2, seed=11, n_flips=64)
s2 = mk()
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter("always")
    s2.resume(ck2)
out["fallback_warned"] = any("falling back" in str(x.message) for x in w)
res = s2.run_supervised(FaultPolicy(sleep=nosleep))
check("corrupt_resume", res)

# 5. shrink to ONE worker: every slot but 0 crashes every round with an
#    aggressive exclusion threshold -> elastic replan down to 1 survivor
s = mk()
inj = FaultInjector(worker_crashes={t: set(range(1, 8))
                                    for t in range(400)})
res = s.run_supervised(FaultPolicy(sleep=nosleep,
                                   worker_failure_threshold=1,
                                   min_workers=1), injector=inj)
check("shrink_to_one", res)
out["excluded"] = sorted(s.supervised_report.excluded_workers)

# 6. graceful degradation: a round that NEVER succeeds; the answer comes
#    back partial (0 < fraction_done < 1) and anytime-valid (no entry
#    better than the exact profile)
s = mk()
inj = FaultInjector(round_failures={2: 10**6})
res = s.run_supervised(FaultPolicy(sleep=nosleep, max_retries=2),
                       injector=inj)
out["degraded"] = s.supervised_report.degraded
out["degraded_frac"] = res.fraction_done
out["degraded_valid"] = bool((np.asarray(res.p) >= pc - 1e-5).all())

# 7. seeded randomized schedules: every one must still land bitwise
seeded_ok = True
for seed in (0, 1, 2):
    s = mk()
    inj = FaultInjector.seeded(seed, n_rounds=64, n_workers=8,
                               p_worker_crash=0.15, p_round_failure=0.3,
                               max_round_failures=2,
                               p_checkpoint_kill=0.2,
                               p_checkpoint_flip=0.2)
    res = s.run_supervised(
        FaultPolicy(sleep=nosleep, checkpoint_every=1,
                    worker_failure_threshold=3),
        checkpoint_path=os.path.join(td, "seed%%d.npz" %% seed),
        injector=inj)
    seeded_ok = (seeded_ok
                 and bool(np.array_equal(np.asarray(res.p), pc))
                 and bool(np.array_equal(np.asarray(res.i), ic)))
out["seeded_bitwise"] = seeded_ok

print(json.dumps(out))
""" % (SRC,)


@pytest.fixture(scope="module")
def results():
    proc = subprocess.run([sys.executable, "-c", _SNIPPET],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_crash_every_round_bitwise(results):
    assert results["crash_every_round_p"] and results["crash_every_round_i"]
    assert results["crash_every_round_frac"] == 1.0
    assert results["crash_replans"] >= 1


def test_transient_failures_retry_to_bitwise(results):
    assert results["transient_p"] and results["transient_i"]
    # ticks 0 and 2 fire (1 + 3 retries); the tick-5 entry lies past the
    # 4-round plan and must never fire
    assert results["retries"] == 4


def test_checkpoint_chaos_does_not_disturb_answer(results):
    assert results["ckpt_chaos_p"] and results["ckpt_chaos_i"]
    assert results["ckpt_failures"] == 1
    assert results["ckpt_corrupted"] == 1
    assert results["ckpt_written"] >= 3


def test_corrupted_checkpoint_resume_falls_back_bitwise(results):
    assert results["fallback_warned"]
    assert results["corrupt_resume_p"] and results["corrupt_resume_i"]


def test_shrink_to_single_worker_bitwise(results):
    assert results["excluded"] == [1, 2, 3, 4, 5, 6, 7]
    assert results["shrink_to_one_p"] and results["shrink_to_one_i"]


def test_graceful_degradation_partial_but_valid(results):
    assert results["degraded"]
    assert 0.0 < results["degraded_frac"] < 1.0
    assert results["degraded_valid"]


def test_seeded_schedules_all_bitwise(results):
    assert results["seeded_bitwise"]
