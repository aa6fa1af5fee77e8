"""flash_decode numerics vs reference attention on 8 fake devices."""

import os
import pathlib
import subprocess
import sys

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.models.flash_decode import flash_decode
from repro.models.layers import attention, repeat_kv

mesh = jax.make_mesh((2, 4), ("data", "model"))
B, S, KV, H, hd = 4, 64, 2, 8, 16
key = jax.random.PRNGKey(0)
ks = jax.random.split(key, 3)
q = jax.random.normal(ks[0], (B, 1, H, hd))
k = jax.random.normal(ks[1], (B, S, KV, hd))
v = jax.random.normal(ks[2], (B, S, KV, hd))
pos = jnp.asarray(37)   # cache filled to 38

with mesh:
    out = jax.jit(lambda q, k, v: flash_decode(
        q, k, v, pos, mesh=mesh, dp_axes=("data",), n_rep=H // KV))(q, k, v)

ref = attention(q, repeat_kv(k, H // KV), repeat_kv(v, H // KV),
                causal=True, offset=pos, kv_len_valid=pos + 1)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                           atol=2e-5)

# sliding-window variant
with mesh:
    outw = jax.jit(lambda q, k, v: flash_decode(
        q, k, v, pos, mesh=mesh, dp_axes=("data",), n_rep=H // KV,
        window=16))(q, k, v)
refw = attention(q, repeat_kv(k, H // KV), repeat_kv(v, H // KV),
                 causal=True, offset=pos, kv_len_valid=pos + 1, window=16)
np.testing.assert_allclose(np.asarray(outw), np.asarray(refw), rtol=2e-5,
                           atol=2e-5)
print("OK")
"""


ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_flash_decode_matches_reference():
    path = [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(p for p in path if p))
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
