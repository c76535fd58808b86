"""Multi-device checks need >1 device => subprocess with the host
platform override (tests themselves must keep seeing 1 device)."""
import os
import subprocess
import sys


SCRIPT = r"""
import numpy as np, jax
from jax.sharding import Mesh
from repro.core.graph import HostGraph
from repro.core import generators as gen
from repro.core.sssp.reference import dijkstra
from repro.core.sssp.engine import run_sssp, SP4_CONFIG, SP3_CONFIG
from repro.core.sssp.distributed import run_sssp_distributed

assert len(jax.devices()) == 8, jax.devices()
n, src, dst, w = gen.make("gnp", 400, seed=11)
hg = HostGraph(n, src, dst, w); g = hg.to_device()
exp = dijkstra(hg).dist
mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
for cfg in (SP4_CONFIG, SP3_CONFIG):
    dd, dc, df, dr = run_sssp_distributed(g, 0, cfg, mesh,
                                          axes=("data", "model"))
    got = np.asarray(dd, np.float64)
    ok = np.allclose(np.where(np.isinf(got), 1e18, got),
                     np.where(np.isinf(exp), 1e18, exp),
                     rtol=1e-5, atol=1e-4)
    assert ok, "distributed != dijkstra"
    single = run_sssp(g, 0, cfg)
    assert np.array_equal(np.asarray(single.dist), np.asarray(dd)), \
        "8-device result must be bitwise identical to 1-device"
print("SUBPROCESS-OK")
"""


def run_with_devices(script: str, n_dev: int = 8) -> str:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"     # never reach for a chip from a child
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_distributed_sssp_8dev_bitwise():
    assert "SUBPROCESS-OK" in run_with_devices(SCRIPT)


TINY_DRYRUN = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.models import transformer as tfm
from repro.distributed import sharding as shr
from repro.optim import adamw_init
from repro.runtime.train_loop import TrainConfig, make_train_step
from functools import partial

mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2, 2),
            ("pod", "data", "model"))
cfg = tfm.LMConfig(name="tiny", n_layers=2, d_model=32, n_heads=4,
                   n_kv_heads=2, d_ff=64, vocab=64, remat=False,
                   param_dtype="float32")
params_abs = jax.eval_shape(partial(tfm.init_params, cfg),
                            jax.random.PRNGKey(0))
p_sh = shr.tree_shardings(params_abs, mesh, shr.lm_param_spec, cfg)
o_sh = shr.opt_state_shardings(p_sh, mesh, params_abs)
opt_abs = jax.eval_shape(adamw_init, params_abs)
hooks = shr.lm_hooks(mesh, cfg)
batch_abs = {"tokens": jax.ShapeDtypeStruct((8, 17), jnp.int32)}
b_sh = {"tokens": NamedSharding(mesh, P(("pod", "data"), None))}
step = make_train_step(lambda p, b: tfm.loss_fn(p, b, cfg, hooks),
                       TrainConfig(), in_shardings=(p_sh, o_sh, b_sh),
                       donate=False)
with mesh:
    compiled = step.lower(params_abs, opt_abs, batch_abs).compile()
txt = compiled.as_text()
assert any(c in txt for c in ("all-reduce", "all-gather")), \
    "expected collectives in multi-pod HLO"
# and it must actually RUN on the 8 fake devices:
params = tfm.init_params(cfg, jax.random.PRNGKey(0))
opt = adamw_init(params)
batch = {"tokens": jnp.asarray(
    np.random.default_rng(0).integers(0, 64, (8, 17)))}
with mesh:
    p2, o2, m = jax.jit(
        lambda p, o, b: step(p, o, b))(params, opt, batch)
assert np.isfinite(float(m["loss"]))
print("TINY-DRYRUN-OK", float(m["loss"]))
"""


def test_multipod_train_step_executes_on_8dev():
    """A miniature of the production multi-pod layout actually RUNS
    (not just compiles) on 8 virtual devices: pod/data/model = 2/2/2."""
    assert "TINY-DRYRUN-OK" in run_with_devices(TINY_DRYRUN)
