"""Plain reference for dense GQA decoders such as ``onerec-0.1b.json``,
and the weights every run serves.

Architecture, as the configuration files state it: token embedding; per
layer RMSNorm -> Q/K/V projections (GQA: query head h reads kv head
h // (H / kvH)) -> rotary embedding on the whole head dim (half-split
rotation, base ``rope_theta``) -> causal softmax attention scaled by
1/sqrt(head_dim) -> output projection -> residual; RMSNorm -> SwiGLU MLP
(silu(x Wg) * (x Wu)) Wd -> residual; final RMSNorm; logits against the
embedding (tied) or a separate head.  GR reads the log-softmax of the
logits over the whole vocabulary; an item's log-prob is the sum of its ND
tokens' log-softmax values, each conditioned on the prompt and the item's
earlier tokens.

This file imports nothing of the program.  The weights are made here, from
the seed, in one jitted call on the device, in float32 (the type they are
served in) and in the nested layout the program's model reads
(``embed``, ``final_norm``, ``lm_head``, and ``dense_layers`` stacked
along a leading layer axis); the program and this reference both read
them.  Initialisation: normal(0, 0.02), output projections
0.02 / sqrt(2 L), norm scales 1.

``logprobs`` runs in float32 with every matmul at ``Precision.HIGHEST``:
the reference.  The same graph also runs one step below a precision a
configuration states, as a control: ``bfloat16`` (weights, activations,
residual stream and keys/values in bfloat16, default matmul precision), or
``float8_e4m3`` (every matmul weight and the logits' head rounded to
float8 e4m3 with one scale per tensor, the rest as ``bfloat16``).  All
keep the norm statistics, the attention softmax and the final log-softmax
in float32, as the program does.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: what this reference computes, as a configuration file names it
ARCHITECTURE = (("attention_kind", "gqa"), ("norm_kind", "rmsnorm"),
                ("act_kind", "swiglu"), ("rope_kind", "rope"))
#: settings it implements at one value only, where a file gives them
FIXED = (("family", "dense"), ("qkv_bias", False), ("rope_fraction", 1.0),
         ("sliding_window", 0), ("length_penalty", 0.0), ("mask_neg", -1e9))
#: keys it reads at any value: the sizes it computes with and the beam
SIZES = frozenset((
    "num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
    "vocab_size", "rope_theta", "norm_eps", "tie_embeddings", "beam_width",
    "top_k", "num_decode_phases", "num_items"))
#: keys of the deployment, not of the model: the catalog, the serving
#: settings, what ``correct`` holds, the longest position a request may
#: reach (rotary embedding has none), and the select algorithms, which
#: choose the same beam (``repro.config.GRConfig``)
DEPLOYMENT = frozenset((
    "name", "source", "reference", "catalog_seed", "serve", "limits",
    "assumed", "dtype", "precision", "control_precision", "max_position",
    "beam_select", "beam_early_term"))


def check(m: dict) -> None:
    """Refuse a configuration this reference does not implement: a ``kind``
    or a setting other than its own, or any key it does not know (a
    latent-attention or expert size, another family's setting)."""
    for key, want in ARCHITECTURE:
        if m[key] != want:
            raise ValueError(f"{m['name']}: {key}={m[key]!r}; the reference "
                             f"{m['reference']} has {want!r}")
    for key, want in FIXED:
        if key in m and m[key] != want:
            raise ValueError(f"{m['name']}: {key}={m[key]!r}; the reference "
                             f"{m['reference']} has {want!r}")
    known = SIZES | DEPLOYMENT | {k for k, _ in ARCHITECTURE + FIXED}
    for key in m:
        if key not in known:
            raise ValueError(f"{m['name']}: {key}={m[key]!r}; the reference "
                             f"{m['reference']} does not implement it")


def _dims(m: dict) -> Tuple:
    return (int(m["num_layers"]), int(m["d_model"]), int(m["num_heads"]),
            int(m["num_kv_heads"]), int(m["head_dim"]), int(m["d_ff"]),
            int(m["vocab_size"]), bool(m["tie_embeddings"]),
            float(m["rope_theta"]), float(m["norm_eps"]))


def param_shapes(m: dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Leaf path -> (shape, init std; 0 = ones)."""
    L, d, H, kvH, hd, ff, V, tied, _, _ = _dims(m)
    out_std = 0.02 / math.sqrt(2 * L)
    s = {
        "embed": ((V, d), 0.02),
        "final_norm/scale": ((d,), 0.0),
        "dense_layers/ln1/scale": ((L, d), 0.0),
        "dense_layers/attn/wq": ((L, d, H * hd), 0.02),
        "dense_layers/attn/wk": ((L, d, kvH * hd), 0.02),
        "dense_layers/attn/wv": ((L, d, kvH * hd), 0.02),
        "dense_layers/attn/wo": ((L, H * hd, d), out_std),
        "dense_layers/ln2/scale": ((L, d), 0.0),
        "dense_layers/mlp/w_gate": ((L, d, ff), 0.02),
        "dense_layers/mlp/w_up": ((L, d, ff), 0.02),
        "dense_layers/mlp/w_down": ((L, ff, d), out_std),
    }
    if not tied:
        s["lm_head"] = ((d, V), 0.02)
    return s


def _nest(flat: Dict[str, jax.Array]) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        *parents, name = path.split("/")
        node = out
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return out


def init_params(m: dict, seed32: int) -> dict:
    """All weights in one jitted call on the default device."""
    shapes = param_shapes(m)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(shapes))
        flat = {}
        for k, (path, (shape, std)) in zip(keys, sorted(shapes.items())):
            flat[path] = (jnp.ones(shape, F32) if std == 0.0 else
                          jax.random.normal(k, shape, F32) * std)
        return _nest(flat)

    return jax.block_until_ready(build(jax.random.PRNGKey(seed32)))


# ------------------------------------------------------------ forward
def _rms(x, scale, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * scale.astype(x.dtype)


def _rope(x, pos, theta):
    """x (..., S, heads, hd), pos (S,) -> half-split rotation."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv                  # (S, hd/2)
    c = jnp.cos(ang)[:, None, :].astype(x.dtype)
    s = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


#: the precisions ``logprobs`` computes in: the reference, and the controls
#: one step below a stated precision (bfloat16 below float32; float8 e4m3
#: weights below bfloat16 matmuls)
PRECISIONS = ("float32", "bfloat16", "float8_e4m3")

_MATMULS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _fp8(w):
    """Weights rounded to float8 e4m3 (4 exponent, 3 mantissa bits) with
    one scale per tensor, its largest magnitude at the format's largest
    normal (240), back in bf16.  ``reduce_precision`` is an explicit
    rounding that XLA keeps; a round trip through a float8 dtype may be
    folded away on the TPU."""
    scale = jnp.max(jnp.abs(w)).astype(F32) / 240.0
    q = jax.lax.reduce_precision(w.astype(F32) / scale, exponent_bits=4,
                                 mantissa_bits=3)
    return (q * scale).astype(jnp.bfloat16)


def _cast(params, precision: str):
    if precision == "float32":
        return params
    if precision == "bfloat16":
        return jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    # float8 e4m3: every matmul weight (per layer), and the logits' head
    out = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    for grp in ("attn", "mlp"):
        for k, w in params["dense_layers"][grp].items():
            if k in _MATMULS:
                out["dense_layers"][grp][k] = jax.vmap(_fp8)(w)
    for k in ("embed", "lm_head"):
        if k in params:
            out[k] = _fp8(params[k])
    return out


def _forward(params, prompt, n, beams, *, dims, precision):
    """Log-softmax over the vocabulary after the prompt (lp0, (V,)), after
    prompt + t0 (lp1, (B, V)) and after prompt + t0 + t1 (lp2, (B, V)) for
    each row ``beams[b] = (t0, t1)``.  ``prompt`` is padded to its static
    length; ``n`` is the real length."""
    L, d, H, kvH, hd, ff, V, tied, theta, eps = dims
    G = H // kvH
    dtype = F32 if precision == "float32" else jnp.bfloat16
    prec = jax.lax.Precision.HIGHEST if dtype == F32 else None
    mm = functools.partial(jnp.einsum, precision=prec)
    p = _cast(params, precision)
    scale = 1.0 / math.sqrt(hd)
    S = prompt.shape[0]
    B = beams.shape[0]
    pos = jnp.arange(S)
    head = p["embed"].T if tied else p["lm_head"]

    def mlp(lp, h):
        g = mm("...d,df->...f", h, lp["mlp"]["w_gate"])
        u = mm("...d,df->...f", h, lp["mlp"]["w_up"])
        return mm("...f,fd->...d", jax.nn.silu(g) * u, lp["mlp"]["w_down"])

    def qkv(lp, h, lead):
        q = mm("...d,df->...f", h, lp["attn"]["wq"]).reshape(
            lead + (kvH, G, hd))
        k = mm("...d,df->...f", h, lp["attn"]["wk"]).reshape(
            lead + (kvH, hd))
        v = mm("...d,df->...f", h, lp["attn"]["wv"]).reshape(
            lead + (kvH, hd))
        return q, k, v

    # ---- prompt: causal attention over its n real tokens
    key_ok = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n)  # (S, S)

    def prompt_layer(x, lp):
        h = _rms(x, lp["ln1"]["scale"], eps)
        q, k, v = qkv(lp, h, (S,))
        q = _rope(q.reshape(S, H, hd), pos, theta).reshape(S, kvH, G, hd)
        k = _rope(k, pos, theta)
        s = mm("skgd,tkd->kgst", q, k).astype(F32) * scale
        a = jax.nn.softmax(jnp.where(key_ok, s, -jnp.inf), axis=-1)
        o = mm("kgst,tkd->skgd", a.astype(dtype), v).reshape(S, H * hd)
        x = x + mm("...f,fd->...d", o, lp["attn"]["wo"])
        x = x + mlp(lp, _rms(x, lp["ln2"]["scale"], eps))
        return x, (k, v)

    x = p["embed"][prompt]
    x, (pk, pv) = jax.lax.scan(prompt_layer, x, p["dense_layers"])
    xl = _rms(x[n - 1], p["final_norm"]["scale"], eps)
    lp0 = jax.nn.log_softmax(mm("d,dv->v", xl, head).astype(F32))

    # ---- beams: two more tokens at positions n, n + 1
    bpos = n + jnp.arange(2)
    own_ok = jnp.arange(2)[None, :] <= jnp.arange(2)[:, None]       # (2, 2)

    def beam_layer(x, xs):
        lp, k_p, v_p = xs
        h = _rms(x, lp["ln1"]["scale"], eps)
        q, k, v = qkv(lp, h, (B, 2))
        q = _rope(q.reshape(B, 2, H, hd), bpos, theta).reshape(
            B, 2, kvH, G, hd)
        k = _rope(k, bpos, theta)
        s_p = mm("bskgd,tkd->bkgst", q, k_p).astype(F32) * scale
        s_p = jnp.where(pos < n, s_p, -jnp.inf)
        s_o = mm("bskgd,btkd->bkgst", q, k).astype(F32) * scale
        s_o = jnp.where(own_ok, s_o, -jnp.inf)
        a = jax.nn.softmax(jnp.concatenate([s_p, s_o], -1), axis=-1)
        a = a.astype(dtype)
        o = (mm("bkgst,tkd->bskgd", a[..., :S], v_p)
             + mm("bkgst,btkd->bskgd", a[..., S:], v)).reshape(B, 2, H * hd)
        x = x + mm("...f,fd->...d", o, lp["attn"]["wo"])
        x = x + mlp(lp, _rms(x, lp["ln2"]["scale"], eps))
        return x, None

    x = p["embed"][beams]
    x, _ = jax.lax.scan(beam_layer, x, (p["dense_layers"], pk, pv))
    x = _rms(x, p["final_norm"]["scale"], eps)
    lp = jax.nn.log_softmax(mm("bsd,dv->bsv", x, head).astype(F32), axis=-1)
    return lp0, lp[:, 0], lp[:, 1]


@functools.lru_cache(maxsize=None)
def _compiled(dims: Tuple, precision: str):
    return jax.jit(functools.partial(_forward, dims=dims,
                                     precision=precision))


def logprobs(params, m: dict, prompt: np.ndarray, beams: np.ndarray,
             s_max: int, rows: int, precision: str = "float32"
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host log-softmax rows for one request: ``prompt`` (n,) padded to
    ``s_max``, ``beams`` (b, 2) padded to ``rows`` (so one program serves
    every request), computed in ``precision`` (one of ``PRECISIONS``).
    Returns (lp0 (V,), lp1 (b, V), lp2 (b, V))."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    n, b = len(prompt), len(beams)
    tok = np.zeros((s_max,), np.int32)
    tok[:n] = prompt
    bm = np.zeros((rows, 2), np.int32)
    bm[:b] = beams
    fn = _compiled(_dims(m), precision)
    lp0, lp1, lp2 = fn(params, jnp.asarray(tok), jnp.int32(n),
                       jnp.asarray(bm))
    return (np.asarray(lp0, np.float64), np.asarray(lp1[:b], np.float64),
            np.asarray(lp2[:b], np.float64))
