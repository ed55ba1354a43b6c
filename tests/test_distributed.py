"""Distributed substrate tests on 8 fake CPU devices (subprocess so the
XLA device-count flag never leaks into this process — smoke tests must see
one device)."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.compression import (dequantize_int8, quantize_int8)

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _run8(code: str) -> dict:
    """Run ``code`` in a subprocess with 8 fake devices; return its JSON."""
    pre = ("import os\n"
           "os.environ['XLA_FLAGS'] = "
           "'--xla_force_host_platform_device_count=8'\n")
    out = subprocess.run(
        [sys.executable, "-c", pre + code], capture_output=True, text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"}, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_quantize_roundtrip_error_bounded():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64,)) * 3)
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x)
    assert float(jnp.max(err)) <= float(s) * 0.5 + 1e-6


def test_sharded_sa_ladder_8dev():
    """The multi-device SA program: champion identical on all shards, and
    the sharded champion is <= every shard's local best (sync exchange)."""
    r = _run8("""
import json, jax, jax.numpy as jnp, numpy as np
from repro.core import SAConfig, sa_minimize
from repro.objectives import functions as F
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
obj = F.schwefel(8)
cfg = SAConfig(T0=50.0, T_min=0.5, rho=0.8, N=10, n_chains=256,
               exchange="sync", record_history=False)
res = sa_minimize(obj, cfg, key=jax.random.PRNGKey(0), mesh=mesh)
res1 = sa_minimize(obj, cfg, key=jax.random.PRNGKey(0), mesh=mesh)
print(json.dumps({
    "f": float(res.f_best),
    "deterministic": float(res.f_best) == float(res1.f_best),
    "err": abs(float(res.f_best) - obj.f_opt),
    "n_dev": len(jax.devices()),
}))
""")
    assert r["n_dev"] == 8
    assert r["deterministic"]
    assert r["err"] < 30.0


def test_compressed_psum_8dev():
    """int8 error-feedback psum: result close to exact psum; residual
    carries the quantization error."""
    r = _run8("""
import json, jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import compressed_psum
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
g = jnp.asarray(np.random.default_rng(0).normal(size=(8, 32)).astype(np.float32))

def body(gl):
    s, resid = compressed_psum(gl, ("data",))
    return s, resid

f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                          out_specs=(P("data"), P("data")),
                          check_vma=False))
s, resid = f(g)
exact = jnp.sum(g, axis=0)
rel = float(jnp.max(jnp.abs(s[0] - exact)) / (jnp.max(jnp.abs(exact)) + 1e-9))
print(json.dumps({"rel_err": rel,
                  "resid_nonzero": bool(jnp.any(resid != 0))}))
""")
    assert r["rel_err"] < 0.05, r


def test_pipeline_2stage_matches_sequential():
    r = _run8("""
import json, jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import make_pipelined_fn, bubble_fraction
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("pod", "data"))
L, D, M, mb = 4, 8, 4, 2   # 4 layers, 2 stages x 2 layers
rng = np.random.default_rng(0)
Ws = jnp.asarray(rng.normal(size=(L, D, D)).astype(np.float32) * 0.3)
x = jnp.asarray(rng.normal(size=(M, mb, D)).astype(np.float32))

def layer_fn(stage_ws, h):
    # stage_ws: this stage's (L/stages, D, D) slice
    for i in range(stage_ws.shape[0]):
        h = jnp.tanh(h @ stage_ws[i])
    return h

def seq_apply(x):
    h = x
    for i in range(L):
        h = jnp.tanh(h @ Ws[i])
    return h

pipe = make_pipelined_fn(layer_fn, mesh, axis="pod")
y_pipe = pipe(Ws, x)
y_seq = jax.vmap(seq_apply)(x)
err = float(jnp.max(jnp.abs(y_pipe - y_seq)))
print(json.dumps({"err": err, "bubble": bubble_fraction(2, M)}))
""")
    assert r["err"] < 1e-5, r
    assert abs(r["bubble"] - (2 - 1) / (4 + 2 - 1)) < 1e-9


def test_straggler_monitor_detects_outlier():
    from repro.distributed.monitor import StragglerMonitor
    mon = StragglerMonitor(zscore=2.0)
    for h in range(8):
        for _ in range(16):
            mon.record(h, 0.1 if h != 5 else 0.5, now=1000.0)
    assert mon.stragglers() == [5]
    assert mon.dead(now=2000.0) == list(range(8))
    assert mon.dead(now=1001.0) == []
