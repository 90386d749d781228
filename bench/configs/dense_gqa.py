"""Plain reference for dense decoder stacks with grouped-query attention:
RMSNorm -> RoPE (rotate-half) GQA attention -> residual -> RMSNorm ->
SwiGLU MLP -> residual, then a final RMSNorm and an untied output head.
This is the published block of InternLM2 and Mistral (and Llama); each
configuration file that names ``"reference": "dense_gqa"`` is run by it.

It imports nothing of the system under test. It reads weights that the
benchmark itself draws (``make_weights``), laid out as the served model
reads them: per-layer tensors stacked on a leading layer axis, matrices
stored (in, out) so that ``y = x @ w``.

``logits_at`` is the float32 forward pass (every matmul at
``Precision.HIGHEST``), one sequence at a time and one kv-head group of
attention at a time, so that it fits beside the weights on the chip.
``fp8=True`` is the lower-precision control: every matmul operand is
rounded to float8 e4m3 with one scale per row of the activation and per
output column of the weight, as an fp8 serving path would compute it.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


# ------------------------------------------------------------------ sizes


def dims(c: dict) -> dict:
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    return {"d": d, "h": h, "kh": c["num_key_value_heads"],
            "hd": c.get("head_dim") or d // h, "f": c["intermediate_size"],
            "v": c["vocab_size"], "n": c["num_hidden_layers"]}


def layer_params(c: dict) -> int:
    """Matmul and norm parameters of one decoder layer."""
    k = dims(c)
    attn = 2 * k["d"] * k["h"] * k["hd"] + 2 * k["d"] * k["kh"] * k["hd"]
    return attn + 3 * k["d"] * k["f"] + 2 * k["d"]


def param_count(c: dict) -> int:
    """Every parameter the chip holds: layers, embedding, untied head and
    the final norm."""
    k = dims(c)
    return k["n"] * layer_params(c) + 2 * k["v"] * k["d"] + k["d"]


def matmul_params_per_token(c: dict) -> int:
    """Parameters one token's matmuls touch in the decoder layers (the
    head is counted apart: only rows whose logits are taken pay it)."""
    k = dims(c)
    return k["n"] * (layer_params(c) - 2 * k["d"])


def page_bytes(c: dict, page: int) -> int:
    """Bytes of one pool page over every layer: int8 key and value codes,
    one float32 scale per token and kv head for each, int32 positions."""
    k = dims(c)
    per_layer = 2 * k["kh"] * page * k["hd"] + 2 * k["kh"] * page * 4 + page * 4
    return per_layer * k["n"]


# ---------------------------------------------------------------- weights


def _init(c: dict, key, dtype):
    k = dims(c)
    n, d, f, v = k["n"], k["d"], k["f"], k["v"]
    qd, kd = k["h"] * k["hd"], k["kh"] * k["hd"]
    ks = iter(jax.random.split(key, 12))

    def mat(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    def norm(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape,
                                               jnp.float32)).astype(dtype)

    return {
        "embed": (0.02 * jax.random.normal(next(ks), (v, d))).astype(dtype),
        "lm_head": (0.02 * jax.random.normal(next(ks), (d, v))).astype(dtype),
        "final_norm": norm((d,)),
        "blocks": {"p0": {
            "ln1": norm((n, d)),
            "ln2": norm((n, d)),
            "mixer": {"wq": mat((n, d, qd), d), "wk": mat((n, d, kd), d),
                      "wv": mat((n, d, kd), d), "wo": mat((n, qd, d), qd)},
            "ffn": {"w_gate": mat((n, d, f), d), "w_up": mat((n, d, f), d),
                    "w_down": mat((n, f, d), f)},
        }},
    }


def make_weights(c: dict, seed: int, dtype=jnp.bfloat16):
    """The model's weights from ``seed``, drawn on the device by one
    compiled call in the served type (no float32 copy of a whole tensor
    is ever held)."""
    state = np.random.SeedSequence(seed).generate_state(2)  # any int seed
    key = jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32),
                                   impl="threefry2x32")
    return jax.jit(partial(_init, c, dtype=dtype))(key)


# --------------------------------------------------------------- forward


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``
    (the reduced axis of the matmul), returned in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / FP8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn)
    return q.astype(jnp.float32) * scale


def _mm(x, w, fp8: bool):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """Rotate-half RoPE on (S, heads, hd)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * freqs  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal attention, one kv head (and its query group) at a time:
    q (S, H, hd), k/v (S, KH, hd) -> (S, H, hd)."""
    s, h, hd = q.shape
    kh = k.shape[1]
    g = h // kh
    qg = q.reshape(s, kh, g, hd).transpose(1, 2, 0, 3)  # (KH, G, S, hd)
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)  # (KH, S, hd)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one(args):
        qh, kk, vv = args  # (G, S, hd), (S, hd), (S, hd)
        sc = jnp.einsum("gqd,kd->gqk", qh, kk, precision=HIGHEST)
        sc = jnp.where(causal, sc / math.sqrt(hd), -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("gqk,kd->gqd", p, vv, precision=HIGHEST)

    out = jax.lax.map(one, (qg, kt, vt))  # (KH, G, S, hd)
    return out.transpose(2, 0, 1, 3).reshape(s, h, hd)


def _logits_at(w, tokens, rows, *, c: dict, fp8: bool):
    k = dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        a = _rms(x, p["ln1"], eps)
        q = _mm(a, p["mixer"]["wq"], fp8).reshape(s, k["h"], k["hd"])
        kk = _mm(a, p["mixer"]["wk"], fp8).reshape(s, k["kh"], k["hd"])
        vv = _mm(a, p["mixer"]["wv"], fp8).reshape(s, k["kh"], k["hd"])
        q, kk = _rope(q, pos, theta), _rope(kk, pos, theta)
        o = _attention(q, kk, vv).reshape(s, k["h"] * k["hd"])
        x = x + _mm(o, p["mixer"]["wo"], fp8)
        a = _rms(x, p["ln2"], eps)
        up = jax.nn.silu(_mm(a, p["ffn"]["w_gate"], fp8)) * _mm(
            a, p["ffn"]["w_up"], fp8)
        return x + _mm(up, p["ffn"]["w_down"], fp8), None

    x, _ = jax.lax.scan(layer, x, w["blocks"]["p0"])
    x = _rms(x[rows], w["final_norm"], eps)
    return _mm(x, w["lm_head"], fp8)


@partial(jax.jit, static_argnames=("c_items", "fp8"))
def _logits_jit(w, tokens, rows, c_items, fp8):
    return _logits_at(w, tokens, rows, c=dict(c_items), fp8=fp8)


def logits_at(w, c: dict, tokens, rows, fp8: bool = False):
    """float32 logits (len(rows), vocab) of the causal forward pass over
    ``tokens`` (S,), read at positions ``rows``. Pad ``tokens`` at the end
    to a fixed length: causal attention keeps pads from touching earlier
    positions, so one compiled program serves every sequence."""
    items = tuple(sorted((kk, vv) for kk, vv in c.items()
                         if isinstance(vv, (int, float)) and not
                         isinstance(vv, bool)))
    return _logits_jit(w, tokens, rows, items, fp8)
