"""A language model's loss as the federated black box (cross-silo).

Client i holds its own token batch; the ZO input x in [0,1]^d, d = hidden
size, shifts the final RMSNorm gains, g = g0 + s (x - 1/2), and
f_i(x) = loss_i(g) / 10, the mean next-token cross-entropy of the client's
batch.  Every query is a whole forward pass.

``build`` makes the weights (bf16, random, in one jitted call on the device)
and the tokens from the key, and hands the program its own LM objective
(``core.model_objectives``) built on them.  The plain reference below is a
float32 forward pass written from the architecture (Qwen2: pre-RMSNorm,
rotary half-split embeddings, causal attention with q/k/v biases, SwiGLU,
tied output embedding) at highest matmul precision; it imports nothing of
the program.  As x moves only the final norm's gains, the reference runs the
24-layer trunk once per client and each value is the head on its output.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from harness import Problem

HIGHEST = jax.lax.Precision.HIGHEST


def _dims(c: dict):
    d, L = c["hidden_size"], c["num_hidden_layers"]
    h, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // h
    return d, L, h, kvh, hd, c["intermediate_size"], c["vocab_size"]


def weight_shapes(c: dict) -> dict:
    """The weights in the layout the program's model takes (layers stacked)."""
    d, L, h, kvh, hd, ff, v = _dims(c)
    q, kv = h * hd, kvh * hd
    return {
        "embed": (v, d), "final_norm": (d,),
        "blocks/attn.ln": (L, d), "blocks/attn.wq": (L, d, q), "blocks/attn.wk": (L, d, kv),
        "blocks/attn.wv": (L, d, kv), "blocks/attn.wo": (L, q, d),
        "blocks/attn.bq": (L, q), "blocks/attn.bk": (L, kv), "blocks/attn.bv": (L, kv),
        "blocks/mlp.ln": (L, d), "blocks/mlp.w_gate": (L, d, ff), "blocks/mlp.w_up": (L, d, ff),
        "blocks/mlp.w_down": (L, ff, d),
    }


def make_weights(c: dict, key) -> dict:
    """Random bf16 weights from ``key``, in one jitted call on the device:
    matrices N(0, 1/fan_in), embedding N(0, 0.02^2), biases N(0, 0.02^2),
    norm gains 1 + N(0, 0.02^2)."""
    shapes = weight_shapes(c)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            if name == "embed" or ".b" in name:
                w = 0.02 * z
            elif name.endswith("ln") or name == "final_norm":
                w = 1.0 + 0.02 * z
            else:
                w = z / math.sqrt(shape[-2])
            out[name] = w.astype(jnp.bfloat16)
        return out

    return make(key)


def make_tokens(c: dict, key):
    n, b, t = c["n_clients"], c["seqs_per_client"], c["tokens_per_seq"]
    toks = jax.jit(lambda k: jax.random.randint(k, (n, b, t + 1), 0, c["vocab_size"],
                                                jnp.int32))(key)
    return toks[..., :-1], toks[..., 1:]


def model_config(c: dict):
    """The program's ``ModelConfig`` for these sizes, at the published rope
    base and norm epsilon."""
    from repro.models.config import ModelConfig

    d, L, h, kvh, hd, ff, v = _dims(c)
    return ModelConfig(name=c["name"], arch_type="dense", n_layers=L, d_model=d, n_heads=h,
                       n_kv_heads=kvh, head_dim=hd, d_ff=ff, vocab_size=v, qkv_bias=True,
                       tie_embeddings=bool(c["tie_word_embeddings"]),
                       rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
                       dtype="bfloat16", source=c["source"])


def query_flops(c: dict) -> float:
    import counts

    return counts.lm_query_flops(c, c["tokens_per_seq"], c["seqs_per_client"])


def build(c: dict, key) -> Problem:
    from repro.core import model_objectives as mobj

    kw, kt = jax.random.split(key)
    weights = make_weights(c, kw)
    tokens, labels = make_tokens(c, kt)
    n = c["n_clients"]
    rep = lambda v: jnp.full((n,), v, jnp.float32)
    cobjs = mobj.LMObjective(batches_tokens=tokens, batches_labels=labels,
                             scale=rep(c["perturb_scale"]), noise_std=rep(c["noise_std"]))
    query, global_value, dim, _ = mobj.make_lm_query(model_config(c), weights)
    data = {"config": c, "weights": weights, "tokens": tokens, "labels": labels}
    return Problem(cobjs=cobjs, query=query, global_value=global_value, dim=dim, fstar=None,
                   data=data)


# ---------------------------------------------------------------------------
# plain reference: float32 forward at highest precision
# ---------------------------------------------------------------------------


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """Rotary embedding, half-split pairs (i, i + hd/2).  x (B, T, H, hd)."""
    t, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]  # (T, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def ref_trunk(W: dict, c: dict, tokens, operands=None):
    """The last layer's output (B, T, d) of a batch (B, T), float32; it does
    not depend on x.  ``operands`` rounds both operands of every matrix
    product (the control's fp8)."""
    d, L, h, kvh, hd, ff, v = _dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    q8 = operands or (lambda a: a)
    mm = lambda spec, a, b: jnp.einsum(spec, q8(a), q8(b), precision=HIGHEST)
    x = W["embed"][tokens]
    B, T = tokens.shape
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, lw):
        a = _rms(x, lw["attn.ln"], eps)
        q = (mm("btd,dq->btq", a, lw["attn.wq"]) + lw["attn.bq"]).reshape(B, T, h, hd)
        k = (mm("btd,dq->btq", a, lw["attn.wk"]) + lw["attn.bk"]).reshape(B, T, kvh, hd)
        vv = (mm("btd,dq->btq", a, lw["attn.wv"]) + lw["attn.bv"]).reshape(B, T, kvh, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        k, vv = jnp.repeat(k, h // kvh, axis=2), jnp.repeat(vv, h // kvh, axis=2)
        s = mm("bqhe,bkhe->bhqk", q, k) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = mm("bhqk,bkhe->bqhe", p, vv).reshape(B, T, h * hd)
        x = x + mm("btq,qd->btd", o, lw["attn.wo"])
        m = _rms(x, lw["mlp.ln"], eps)
        u = jax.nn.silu(mm("btd,df->btf", m, lw["mlp.w_gate"])) * mm("btd,df->btf", m,
                                                                      lw["mlp.w_up"])
        return x + mm("btf,fd->btd", u, lw["mlp.w_down"]), None

    layers = {k[len("blocks/"):]: w for k, w in W.items() if k.startswith("blocks/")}
    x, _ = jax.lax.scan(layer, x, layers)
    return x


def ref_head(W: dict, c: dict, x, labels, gains, operands=None):
    """Mean next-token cross-entropy from the trunk's output ``x``: the final
    norm with ``gains``, the tied output projection, float32."""
    q8 = operands or (lambda a: a)
    logits = jnp.einsum("btd,vd->btv", q8(_rms(x, gains, c["rms_norm_eps"])), q8(W["embed"]),
                        precision=HIGHEST)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def fp8(w):
    """Round to float8 e4m3 with one scale per tensor, to its largest value:
    the precision below the configuration's bfloat16 (the control rounds
    both operands of every matrix product so)."""
    s = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / 448.0
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _gains(params, c: dict, x):
    """The gains are a bf16 weight: g = bf16(g0 + bf16(s (x - 1/2)))."""
    delta = (c["perturb_scale"] * (x - 0.5)).astype(jnp.bfloat16)
    return (params["g0"] + delta).astype(jnp.float32)


def ref_model(data: dict, quantize=None):
    """(per-client arrays, shared parameters, query(params, ci, x, key),
    value(params, ci, x)) of the reference.  The trunk does not depend on x:
    it runs once per client here, and a value is the head on its output.
    The float32 weights are the shared parameters, handed to every jitted
    function as an argument: a closed-over array would be compiled into the
    executable."""
    c = data["config"]
    n = c["n_clients"]
    params = {"W": {k: v.astype(jnp.float32) for k, v in data["weights"].items()},
              "g0": data["weights"]["final_norm"]}
    trunk = jax.jit(lambda W, tokens: ref_trunk(W, c, tokens, quantize))
    clients = {"hidden": jnp.stack([trunk(params["W"], data["tokens"][i]) for i in range(n)]),
               "labels": data["labels"],
               "noise": jnp.full((n,), c["noise_std"], jnp.float32)}

    def value(params, ci, x):
        return ref_head(params["W"], c, ci["hidden"], ci["labels"], _gains(params, c, x),
                        quantize) / 10.0

    def query(params, ci, x, key):
        return value(params, ci, x) + ci["noise"] * jax.random.normal(key, ())

    return clients, params, query, value


def ref_global_value(data: dict, xs, quantize=None) -> np.ndarray:
    """F at each row of xs, float32 at highest precision, on the device."""
    clients, params, _, value = ref_model(data, quantize)
    f = jax.jit(lambda params, clients, x: jnp.mean(
        jax.vmap(lambda ci: value(params, ci, x))(clients)))
    with jax.default_matmul_precision("highest"):
        return np.array([float(f(params, clients, jnp.asarray(x))) for x in np.asarray(xs)])


def f_gaps(data: dict, jobs, problem: Problem, quantize=None) -> np.ndarray:
    """Relative gaps |F_program(x_r) - F(x_r)| / |F(x_r)| at every round of
    every job; ``quantize`` puts the lower-precision reference (the control)
    in the program's place."""
    xs = np.concatenate([np.asarray(j.result.xs) for j in jobs])
    f_prog = np.concatenate([np.asarray(j.result.f_values) for j in jobs])
    if quantize is not None:
        f_prog = ref_global_value(data, xs, quantize)
    f_ref = ref_global_value(data, xs)
    return np.abs(f_prog - f_ref) / np.abs(f_ref)


def ref_queries(data: dict, xs, keys, quantize=None) -> np.ndarray:
    """Each client's noisy answers at its points xs (N, k, d) under the query
    keys (N, k, 2), float32 at highest."""
    clients, params, query, _ = ref_model(data, quantize)
    answers = jax.jit(lambda params, ci, xs, keys: jax.lax.map(
        lambda a: query(params, ci, a[0], a[1]), (xs, keys)))
    out = []
    for i in range(np.shape(xs)[0]):
        ci = jax.tree_util.tree_map(lambda a: a[i], clients)
        out.append(np.asarray(answers(params, ci, jnp.asarray(xs[i]), jnp.asarray(keys[i]))))
    return np.stack(out)
