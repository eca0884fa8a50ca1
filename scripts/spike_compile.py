"""Spike: can we lower+compile a big scanned transformer on 512 host devices in reasonable time?"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"  # compile-only: never take the chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import time
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from functools import partial

from repro.launch.mesh import make_production_mesh

t0 = time.time()
mesh = make_production_mesh(multi_pod=True)
print(f"mesh built {time.time()-t0:.1f}s ndev={len(jax.devices())}")

L, D, F, H, V = 32, 4096, 14336, 32, 128256
B, S = 256, 4096

def init_specs():
    params = {
        "emb": jax.ShapeDtypeStruct((V, D), jnp.bfloat16),
        "wq": jax.ShapeDtypeStruct((L, D, D), jnp.bfloat16),
        "wk": jax.ShapeDtypeStruct((L, D, D), jnp.bfloat16),
        "wv": jax.ShapeDtypeStruct((L, D, D), jnp.bfloat16),
        "wo": jax.ShapeDtypeStruct((L, D, D), jnp.bfloat16),
        "w1": jax.ShapeDtypeStruct((L, D, F), jnp.bfloat16),
        "w2": jax.ShapeDtypeStruct((L, F, D), jnp.bfloat16),
    }
    return params

p_specs = {
    "emb": P("model", None),
    "wq": P(None, "data", "model"),
    "wk": P(None, "data", "model"),
    "wv": P(None, "data", "model"),
    "wo": P(None, "model", "data"),
    "w1": P(None, "data", "model"),
    "w2": P(None, "model", "data"),
}

def layer(x, w):
    wq, wk, wv, wo, w1, w2 = w
    q = x @ wq
    k = x @ wk
    v = x @ wv
    q = q.reshape(*q.shape[:-1], H, D // H)
    k = k.reshape(*k.shape[:-1], H, D // H)
    v = v.reshape(*v.shape[:-1], H, D // H)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(D // H)
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
    s = jnp.where(mask, s, -1e9)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(x.shape)
    x = x + o @ wo
    h = jax.nn.gelu(x @ w1)
    x = x + h @ w2
    return x, None

def loss_fn(params, tokens, labels):
    x = params["emb"][tokens]
    ws = (params["wq"], params["wk"], params["wv"], params["wo"], params["w1"], params["w2"])
    x, _ = jax.lax.scan(lambda c, w: layer(c, w), x, ws)
    logits = x @ params["emb"].T
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()

def train_step(params, tokens, labels):
    g = jax.grad(loss_fn)(params, tokens, labels)
    return jax.tree.map(lambda p, gg: p - 1e-3 * gg.astype(p.dtype), params, g)

in_shardings = (
    {k: NamedSharding(mesh, v) for k, v in p_specs.items()},
    NamedSharding(mesh, P(("pod", "data"), None)),
    NamedSharding(mesh, P(("pod", "data"), None)),
)
out_shardings = {k: NamedSharding(mesh, v) for k, v in p_specs.items()}

tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
t1 = time.time()
lowered = jax.jit(train_step, in_shardings=in_shardings, out_shardings=out_shardings).lower(init_specs(), tok, tok)
print(f"lowered in {time.time()-t1:.1f}s")
t2 = time.time()
compiled = lowered.compile()
print(f"compiled in {time.time()-t2:.1f}s")
ma = compiled.memory_analysis()
print("memory_analysis:", ma)
ca = compiled.cost_analysis()
print("cost flops:", ca.get("flops", None) if ca else None)
txt = compiled.as_text()
import re
colls = re.findall(r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)", txt)
from collections import Counter
print("collectives:", Counter(colls))
print(f"TOTAL {time.time()-t0:.1f}s")
