"""Pallas viability probe on a TPU: (1) sequential-grid scan with VMEM
scratch carry — per-step cost; (2) int64 inside a kernel."""
import os, sys, time
os.environ.setdefault("JAX_ENABLE_X64", "1")
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N = 5120  # padded node axis
B = 512

def kernel(req_ref, alloc_ref, out_ref, util_ref):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        util_ref[:] = jnp.zeros_like(util_ref)

    req = req_ref[b, 0]
    util = util_ref[0, :]
    fits = util + req <= alloc_ref[0, :]
    score = jnp.where(fits, alloc_ref[0, :] - util, -1.0)
    best = jax.lax.argmax(score, 0, jnp.int32)
    # one-hot vector accumulate (scalar scatters to VMEM are unsupported)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)[0]
    util_ref[0, :] = util + jnp.where(lane == best, req, 0.0)
    out_ref[b, :] = jnp.full((128,), best, jnp.int32)

@jax.jit
def run(req, alloc):
    return pl.pallas_call(
        kernel,
        grid=(B,),
        out_shape=jax.ShapeDtypeStruct((B, 128), jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((1, N), jnp.float32)],
    )(req, alloc)

req = jnp.ones((B, 1), jnp.float32) * 0.5
alloc = jnp.ones((1, N), jnp.float32) * 3.0
out = run(req, alloc)
jax.block_until_ready(out)
ts = []
for _ in range(3):
    t0 = time.perf_counter()
    jax.block_until_ready(run(req, alloc))
    ts.append(time.perf_counter() - t0)
o = np.asarray(out)[:, 0]
print(f"pallas scan B={B}: {min(ts)*1e3:.1f}ms ({min(ts)/B*1e6:.1f} us/pod); "
      f"first 8 decisions: {o[:8]}")
# each node fits 6 pods of 0.5 in 3.0: decisions should rotate as nodes fill
assert len(set(o.tolist())) > 1 or B <= 6

# int64 probe
def k64(a_ref, o_ref):
    o_ref[:] = a_ref[:] * 2 + 1

try:
    a = jnp.arange(8 * 128, dtype=jnp.int64).reshape(8, 128)
    r = pl.pallas_call(
        k64,
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.int64),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
    )(a)
    print("int64 in pallas: OK", np.asarray(r)[0, :3])
except Exception as e:
    print("int64 in pallas FAILED:", type(e).__name__, str(e)[:200])
