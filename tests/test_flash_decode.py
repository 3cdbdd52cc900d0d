"""MLA flash-decode (shard_map over a sequence-sharded latent cache) must
match the baseline decode path exactly. Runs in a subprocess so the forced
8-device host platform never leaks into other tests."""
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import get_arch
from repro.models import build_model
from repro.sharding.act import activation_sharding

cfg = get_arch('deepseek-v2-236b').smoke.replace(dtype='float32',
                                                 remat='none')
cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
model = build_model(cfg)
key = jax.random.PRNGKey(0)
B, T = 4, 13
toks = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
params = model.init(key)
maxs = 32
_, cache = model.prefill(params, {'tokens': toks[:, :T-1]}, maxs)
lg_base, _ = model.decode_step(params, cache, toks[:, T-1],
                               jnp.asarray(T-1, jnp.int32))
mesh = jax.make_mesh((2, 4), ("data", "model"), devices=jax.devices()[:8])
model2 = build_model(cfg.replace(flash_decode=True))
with jax.set_mesh(mesh), activation_sharding(mesh):
    _, cache2 = model2.prefill(params, {'tokens': toks[:, :T-1]}, maxs)
    lg_flash, _ = jax.jit(model2.decode_step)(params, cache2, toks[:, T-1],
                                              jnp.asarray(T-1, jnp.int32))
rel = np.abs(np.asarray(lg_flash) - np.asarray(lg_base)).max() / (
    np.abs(np.asarray(lg_base)).max() + 1e-9)
assert rel < 2e-3, rel
print("FLASH_DECODE_OK", rel)
"""


def test_mla_flash_decode_matches_baseline():
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FLASH_DECODE_OK" in r.stdout
