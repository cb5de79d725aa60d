"""Expert-parallel MoE correctness: EP (experts sharded over `model`) must
produce the same outputs as TP and as the unsharded local path. Runs in a
subprocess with 4 forced devices (mesh 2 data x 2 model)."""

import json
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_SNIPPET = r"""
import os, json, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, %r)
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.models import moe
from repro.models.common import init_params, sanitized_pspecs
from repro.models.moe import ShardCtx

cfg = configs.get_smoke("olmoe-1b-7b")   # 8 experts top-2, d=64
mesh = jax.make_mesh((2, 2), ("data", "model"), (jax.sharding.AxisType.Auto,) * 2)
spec = moe.moe_spec(cfg)
params = init_params(jax.random.key(0), spec)
x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model), jnp.float32)

ref, aux_ref = moe._moe_local(cfg, params, x, None)

def run(rules):
    ctx = ShardCtx(mesh=mesh, dp=("data",), tp="model", rules=rules)
    out, aux = jax.jit(lambda p, xx: moe.moe_ffn(cfg, p, xx, ctx))(params, x)
    return np.asarray(out), float(aux)

base = {"batch": ("data",), "mlp": None, "experts": None}
out_dp, _ = run(base)
out_tp, _ = run(dict(base, mlp="model"))
out_ep, _ = run(dict(base, experts="model"))

res = {
    "dp_err": float(np.abs(out_dp - np.asarray(ref)).max()),
    "tp_err": float(np.abs(out_tp - np.asarray(ref)).max()),
    "ep_err": float(np.abs(out_ep - np.asarray(ref)).max()),
    "scale": float(np.abs(np.asarray(ref)).max()),
}
print(json.dumps(res))
""" % (SRC,)


@pytest.fixture(scope="module")
def results():
    proc = subprocess.run([sys.executable, "-c", _SNIPPET],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_dp_matches_local(results):
    assert results["dp_err"] < 1e-4 * max(results["scale"], 1)


def test_tp_matches_local(results):
    assert results["tp_err"] < 1e-4 * max(results["scale"], 1)


def test_ep_matches_local(results):
    assert results["ep_err"] < 1e-4 * max(results["scale"], 1)
