"""Targeted probe: int8 jnp chain vs the Pallas int8 chain, by shape.

Settles the width-gate question raised in review
(kernels/quantized.py): where exactly does the Pallas whole-chain
kernel stop paying? Not measured on today's code. The one round-4 run on
a v5e found no sharp crossover at uniform widths and a decisive jnp win
only when interior dims sit below the 128-lane MXU tile; a narrow
classifier head did not matter. Timing as tools/kernel_sweep.py.
"""
import time, json, sys
import jax, jax.numpy as jnp, numpy as np
from jax import lax
from tpu_dist_nn.kernels.quantized import (fcnn_quantized_forward, forward_quantized, quantize_fcnn)
from tpu_dist_nn.models.fcnn import init_fcnn

@jax.jit
def _trivial(seed): return seed * jnp.float32(2.0) + jnp.float32(1.0)
np.asarray(_trivial(jnp.float32(0.5)))
def t_once(f):
    t0=time.monotonic(); f(); return time.monotonic()-t0
floor = min(t_once(lambda i=i: np.asarray(_trivial(jnp.float32(1000.0+i)))) for i in range(5))
sc=[float(np.random.default_rng().integers(1<<20))]
def measure(fn, x, iters):
    for _ in range(4):
        @jax.jit
        def run(bx, seed, _k=iters):
            def body(_, c):
                eps, acc = c
                out = fn(bx + eps); s = out.reshape(-1)[0].astype(jnp.float32)
                return (s*jnp.float32(1e-30)).astype(bx.dtype), acc+s
            o0 = fn(bx + (seed*jnp.float32(1e-30)).astype(bx.dtype))
            s0 = o0.reshape(-1)[0].astype(jnp.float32)
            _, acc = lax.fori_loop(0, _k, body, ((s0*jnp.float32(1e-30)).astype(bx.dtype), s0))
            return acc
        def timed():
            sc[0]+=1.0; s=jnp.float32(sc[0])
            t0=time.monotonic(); np.asarray(run(x,s)); return time.monotonic()-t0
        timed()
        best = min(timed() for _ in range(3))
        sig = best-floor
        if sig >= 0.1: return sig/(iters+1), iters
        per = max(sig, 0.002)/(iters+1); iters = min(int(0.25/per), iters*20)
    return None, iters
batch=8192
out={}
for w in (128, 192, 256, 384, 512):
    params = init_fcnn(jax.random.key(0), [w,w,w,w])
    qp = quantize_fcnn(params); acts=("relu","relu","softmax")
    x = jax.device_put(jnp.asarray(np.random.default_rng(1).uniform(0,1,(batch,w)), jnp.float32))
    r={}
    for name, fn in (("jnp", lambda bx,q=qp: forward_quantized(q,bx,acts)),
                     ("pallas", lambda bx,q=qp: fcnn_quantized_forward(q,bx,activations=acts,prefer_kernel=True))):
        try: t,_ = measure(fn, x, 200)
        except Exception as e: t=None; print(f"# w={w} {name}: {e}", file=sys.stderr)
        r[name]= round(t,9) if t else None
    r["pallas_vs_jnp"] = round(r["jnp"]/r["pallas"],3) if r["jnp"] and r["pallas"] else None
    out[w]=r; print(json.dumps({w:r}), flush=True)
# head-shape check: wide hidden, narrow head
for dims in ([1024,1024,1024,10], [512,512,512,10]):
    params = init_fcnn(jax.random.key(0), dims)
    qp = quantize_fcnn(params); acts=("relu","relu","softmax")
    x = jax.device_put(jnp.asarray(np.random.default_rng(1).uniform(0,1,(batch,dims[0])), jnp.float32))
    r={}
    for name, fn in (("jnp", lambda bx,q=qp: forward_quantized(q,bx,acts)),
                     ("pallas", lambda bx,q=qp: fcnn_quantized_forward(q,bx,activations=acts,prefer_kernel=True))):
        try: t,_ = measure(fn, x, 200)
        except Exception as e: t=None; print(f"# {dims} {name}: {e}", file=sys.stderr)
        r[name]= round(t,9) if t else None
    r["pallas_vs_jnp"] = round(r["jnp"]/r["pallas"],3) if r["jnp"] and r["pallas"] else None
    print(json.dumps({str(dims):r}), flush=True)
