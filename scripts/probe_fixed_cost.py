"""Is the pallas dispatch's ~580ms fixed cost arg staging or program
complexity? Same signature as the real kernel, trivial body."""
import os, sys, time
os.environ.setdefault("JAX_ENABLE_X64", "1")
import jax
jax.config.update("jax_enable_x64", True)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Np, VZ, TCp, LANE, SUB, Bp = 5248, 128, 32, 128, 8, 1024

def kernel(breal, tmpl, sc, mf, ms,
           alloc, stat, onehot, regrow, zvnode, zvalid, konnf, konns,
           shasall, validn, rowt, eye, prowf, prows,
           req_in, nzpc_in, cntfn_in, cntsn_in,
           out_ref, req_o, nzpc_o, cntfn_o, cntsn_o):
    req_o[:] = req_in[:]
    nzpc_o[:] = nzpc_in[:]
    cntfn_o[:] = cntfn_in[:]
    cntsn_o[:] = cntsn_in[:]
    out_ref[:] = jnp.full((SUB, Bp), -1, jnp.int32)
    def body(b, _):
        out_ref[:] = out_ref[:] + jnp.int32(1)
        return jnp.int32(0)
    jax.lax.fori_loop(jnp.int32(0), breal[0], body, jnp.int32(0))

vm = pl.BlockSpec(memory_space=pltpu.VMEM)
sm = pl.BlockSpec(memory_space=pltpu.SMEM)
carr = [jnp.zeros((16, Np), jnp.int32), jnp.zeros((8, Np), jnp.int32),
        jnp.zeros((TCp, Np), jnp.int32), jnp.zeros((TCp, Np), jnp.int32)]
out_shape = (jax.ShapeDtypeStruct((SUB, Bp), jnp.int32),
             *[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in carr])
statics = [jnp.zeros((16, Np), jnp.int32), jnp.zeros((32, Np), jnp.int32),
           jnp.zeros((1, Np, VZ), jnp.float32), jnp.zeros((TCp, Np), jnp.int32),
           jnp.zeros((TCp, Np), jnp.int32), jnp.zeros((TCp, VZ), jnp.int32),
           jnp.zeros((TCp, Np), jnp.int32), jnp.zeros((TCp, Np), jnp.int32),
           jnp.zeros((8, Np), jnp.int32), jnp.zeros((SUB, Np), jnp.int32),
           jnp.zeros((4, TCp, VZ), jnp.int32), jnp.zeros((TCp, LANE), jnp.float32),
           jnp.zeros((TCp, Np), jnp.int32), jnp.zeros((TCp, Np), jnp.int32)]

@jax.jit
def run(carry, breal, tmpl, mf, ms):
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel, out_shape=out_shape,
            in_specs=[sm, sm, sm, vm, vm] + [vm] * 14 + [vm] * 4,
            out_specs=tuple([vm] * 5),
            input_output_aliases={19 + i: 1 + i for i in range(4)},
        )(breal, tmpl, jnp.zeros(216, jnp.int32), mf, ms, *statics, *carry)

breal = jnp.asarray([Bp], jnp.int32)
tmpl = jnp.zeros(Bp, jnp.int32)
mf = jnp.zeros((Bp, LANE), jnp.int32)
ms = jnp.zeros((Bp, LANE), jnp.int32)
r = run(carr, breal, tmpl, mf, ms)
jax.block_until_ready(r[0])
carr = list(r[1:])
ts = []
for _ in range(4):
    t0 = time.perf_counter()
    r = run(carr, breal, tmpl, mf, ms)
    jax.block_until_ready(r[0])
    carr = list(r[1:])
    ts.append(time.perf_counter() - t0)
print(f"same-signature tiny kernel, {Bp} loop iters: {min(ts)*1e3:.1f}ms")
