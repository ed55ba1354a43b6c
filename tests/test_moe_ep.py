"""Expert-parallel MoE dispatch == local MoE (values and gradients), on 8
fake devices in a subprocess.  This is the correctness guarantee behind the
EP cells of the dry-run (deepseek, kimi, jamba)."""
import json
import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _run8(code: str) -> dict:
    pre = ("import os\n"
           "os.environ['XLA_FLAGS'] = "
           "'--xla_force_host_platform_device_count=8'\n")
    out = subprocess.run(
        [sys.executable, "-c", pre + code], capture_output=True, text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"}, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_ep_matches_local_forward_and_grad():
    r = _run8("""
import json
from functools import partial
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.models import layers as L
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("model",))
E, D, F, top_k = 16, 8, 16, 2
key = jax.random.PRNGKey(0)
p = L.init_moe(key, D, F, E, 0, F, jnp.float32)
routed = {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, D), jnp.float32)

# generous capacity so EP and local keep identical token sets
kw = dict(top_k=top_k, capacity_factor=8.0)

def local_loss(rp, x):
    return jnp.sum(L.moe_apply(rp, x, **kw) ** 2)

def ep_loss(rp, x):
    fn = partial(L.moe_apply, **kw, ep_axis="model", ep_size=8)
    y = jax.shard_map(fn, mesh=mesh,
                  in_specs=({"router": P(), "w_gate": P("model"),
                             "w_up": P("model"), "w_down": P("model")},
                            P()),
                  out_specs=P(), check_vma=False)(rp, x)
    return jnp.sum(y ** 2)

l0, g0 = jax.value_and_grad(local_loss)(routed, x)
l1, g1 = jax.value_and_grad(ep_loss)(routed, x)
gerr = max(float(jnp.max(jnp.abs(g0[k] - g1[k]))) for k in g0)
gmag = max(float(jnp.max(jnp.abs(g0[k]))) for k in g0)
print(json.dumps({"l0": float(l0), "l1": float(l1),
                  "gerr_rel": gerr / (gmag + 1e-9)}))
""")
    assert abs(r["l0"] - r["l1"]) / (abs(r["l0"]) + 1e-9) < 1e-5, r
    assert r["gerr_rel"] < 1e-5, r
