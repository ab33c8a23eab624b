"""Distribution correctness on a small fake-device mesh (subprocess: the
smoke-test process must keep seeing exactly 1 device)."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs import get_config
from repro.models import Transformer
from repro.optim.adamw import AdamWConfig
from repro.runtime import train_lib, serve_lib, elastic
from repro.runtime.sharding_rules import param_specs

out = {}
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
cfg = get_config("qwen2-0.5b").smoke()
model = Transformer(cfg)
acfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)

# --- sharded train step runs and matches the unsharded step ------------------
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0,
                                      cfg.vocab_size)}
batch_sds = {"tokens": jax.ShapeDtypeStruct((4, 17), jnp.int32)}
state = train_lib.init_state(model, jax.random.PRNGKey(0), acfg)
step_m, (st_sh, _) = train_lib.build_train_step(
    model, mesh, acfg, train_lib.TrainOpts(donate=False), batch_sds=batch_sds)
state_m = jax.device_put(state, st_sh)
new_m, met_m = step_m(state_m, batch)

step_1, _ = train_lib.build_train_step(model, None, acfg,
                                       train_lib.TrainOpts(donate=False))
new_1, met_1 = step_1(state, batch)
out["loss_mesh"] = float(met_m["loss"])
out["loss_single"] = float(met_1["loss"])
out["loss_diff"] = abs(out["loss_mesh"] - out["loss_single"])

# --- decode step with sharded cache -----------------------------------------
dec = serve_lib.build_decode_step(model, mesh, batch=4, max_len=16,
                                  donate=False)
params_sh = jax.device_put(state["params"], param_specs(model.schema(), mesh))
cache = model.init_cache(4, 16)
toks = jnp.zeros((4,), jnp.int32)
logits, cache2 = dec(params_sh, cache, toks)
out["decode_logits_finite"] = bool(jnp.isfinite(logits).all())

# --- elastic remesh 8 -> 4 devices -------------------------------------------
small = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
state_small = elastic.remesh_state(state, model.schema(), small)
step_s, _ = train_lib.build_train_step(model, small, acfg,
                                       train_lib.TrainOpts(donate=False))
new_s, met_s = step_s(state_small, batch)
out["loss_remesh"] = float(met_s["loss"])
out["remesh_diff"] = abs(out["loss_remesh"] - out["loss_single"])

# --- other block families shard correctly too (MoE / hybrid / SSM) ----------
fam_diffs = {}
for arch in ("granite-moe-1b-a400m", "recurrentgemma-9b", "mamba2-130m"):
    fcfg = get_config(arch).smoke()
    fmodel = Transformer(fcfg)
    fb = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (4, 17), 0,
                                       fcfg.vocab_size)}
    fsds = {"tokens": jax.ShapeDtypeStruct((4, 17), jnp.int32)}
    fstate = train_lib.init_state(fmodel, jax.random.PRNGKey(0), acfg)
    fstep_m, (fsh, _) = train_lib.build_train_step(
        fmodel, mesh, acfg, train_lib.TrainOpts(donate=False), batch_sds=fsds)
    _, fm = fstep_m(jax.device_put(fstate, fsh), fb)
    fstep_1, _ = train_lib.build_train_step(fmodel, None, acfg,
                                            train_lib.TrainOpts(donate=False))
    _, f1 = fstep_1(fstate, fb)
    fam_diffs[arch] = abs(float(fm["loss"]) - float(f1["loss"]))
out["family_diffs"] = fam_diffs

print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # the child wants 8 fake host devices and must never reach for a chip
    # (this process may already hold it)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(repo, "src"))
    # the 8-fake-device script compiles several model families; on a loaded
    # CPU host it sits just under 9 minutes, so leave real headroom
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                          text=True, timeout=1200, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_sharded_step_matches_single_device(result):
    assert result["loss_diff"] < 1e-3


def test_sharded_decode_finite(result):
    assert result["decode_logits_finite"]


def test_elastic_remesh_preserves_computation(result):
    assert result["remesh_diff"] < 1e-3


def test_moe_hybrid_ssm_families_shard_correctly(result):
    for arch, diff in result["family_diffs"].items():
        assert diff < 1e-3, (arch, diff)
