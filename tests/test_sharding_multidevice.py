"""Sharding planner rules (on the abstract production mesh) and true
multi-device SPMD semantics (8 host devices in a subprocess)."""

import os
import subprocess
import sys

import jax
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import configs
from repro.launch.shardings import Planner
from repro.models import init_params
from repro.optim import AdamW
from repro.runtime.train_step import init_train_state

MESH = AbstractMesh((16, 16), ("data", "model"))


def _specs(arch):
    cfg = configs.get(arch)
    planner = Planner(MESH, cfg)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    return cfg, planner.param_specs(shapes), shapes


def test_dense_param_rules():
    cfg, specs, shapes = _specs("yi-34b")
    assert specs["embed"] == P("model", "data")        # vocab 64000 % 16 == 0
    assert specs["lm_head"] == P("data", "model")
    lay = specs["layers"]
    assert lay["attn"]["wq"] == P(None, "data", "model")
    assert lay["attn"]["wo"] == P(None, "model", "data")
    assert lay["ffn"]["w_down"] == P(None, "model", "data")
    assert lay["norm1"]["w"] == P(None, None)          # replicated


def test_nondivisible_vocab_falls_back():
    cfg, specs, _ = _specs("internvl2-2b")             # vocab 92553 odd
    assert specs["embed"] == P(None, "data")


def test_moe_expert_rules():
    cfg, specs, _ = _specs("mixtral-8x7b")
    lay = specs["layers"]
    # 8 experts don't divide the 16-way model axis → TP inside experts
    assert lay["ffn"]["w_gate"] == P(None, None, "data", "model")
    assert lay["ffn"]["w_down"] == P(None, None, "model", "data")


def test_optimizer_state_mirrors_params():
    cfg = configs.get("qwen3-32b")
    planner = Planner(MESH, cfg)
    state_shape = jax.eval_shape(
        lambda k: init_train_state(k, cfg, AdamW()), jax.random.PRNGKey(0))
    specs = planner.state_specs(state_shape)
    assert specs.params["embed"] == specs.opt_state.m["embed"]
    assert specs.params["layers"]["ffn"]["w_up"] == \
        specs.opt_state.v["layers"]["ffn"]["w_up"]
    assert specs.step == P()


def test_cache_specs_decode_and_long():
    cfg = configs.get("yi-34b")
    planner = Planner(MESH, cfg)
    from repro.models import init_cache
    cache = jax.eval_shape(lambda: init_cache(cfg, 128, 32768))
    specs = planner.cache_specs(cache, 128)
    assert specs["k"] == P(None, ("data",), None, "model", None) or \
        specs["k"] == P(None, ("data",), None, "model", None)
    # long_500k: batch=1 → sequence sharded over both axes
    cache1 = jax.eval_shape(lambda: init_cache(cfg, 1, 524288))
    specs1 = planner.cache_specs(cache1, 1)
    assert specs1["k"][3] == ("data", "model")


MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp, json
from collections import Counter
from repro.core.mapreduce import wordcount_map_factory
from repro.pipeline import Pipeline

rng = np.random.default_rng(0)
W, n_keys, n_per = 8, 64, 512
keys = rng.integers(0, n_keys, (W, n_per)).astype(np.int32)
vals = np.ones_like(keys)
shard = np.stack([keys, vals], -1).reshape(W * n_per, 2)

mesh = jax.make_mesh((8,), ("workers",))
map_fn = wordcount_map_factory(n_keys)
agg = (Pipeline.from_source(shards=shard).map(map_fn).reduce("sum")
       .build(num_buckets=n_keys, n_workers=8, backend="shard_map",
              mesh=mesh))
res, _stats = agg.run_batch(data=shard)
res = np.asarray(res)
want = np.zeros(n_keys)
for k in keys.ravel():
    want[k] += 1
assert np.allclose(res, want), "aggregate mismatch"

grp = (Pipeline.from_source(shards=shard).map(map_fn)
       .reduce("sum", mode="group", capacity=2048)
       .build(num_buckets=n_keys, n_workers=8, backend="shard_map",
              mesh=mesh))
(gk, gv, gvalid), _gstats = grp.run_batch(data=shard)
got = {int(k): float(v) for k, v, ok in
       zip(np.asarray(gk), np.asarray(gv), np.asarray(gvalid)) if ok}
assert got == {i: float(want[i]) for i in range(n_keys) if want[i] > 0}
print("MULTIDEVICE_OK")
"""


def test_shard_map_backend_on_8_devices():
    """Real SPMD (not vmap simulation): 8 host devices in a subprocess."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert "MULTIDEVICE_OK" in out.stdout, out.stderr[-2000:]
