"""Plain float32 reference of a StableLM decoder, and the seeded weights
that the benchmark serves it with.

The architecture is the published one of Hugging Face ``model_type``
``stablelm`` (stabilityai/stablelm-3b-4e1t): token embedding, then per layer
a pre-LayerNorm causal self-attention with partial rotary embeddings
(``partial_rotary_factor`` of each head, rotate-half convention, no q/k/v
bias) and a pre-LayerNorm SwiGLU MLP, each added to the residual stream;
a final LayerNorm and an untied output head.

This module imports nothing of the system under test.  The weights are made
here from the seed, in the benchmark's own layout (:func:`weight_shapes`);
the serve driver hands the same arrays to the program in the program's
layout.  Every matrix product runs at ``Precision.HIGHEST`` in float32.

:func:`make_control` is the same forward pass with every matmul operand and
the K/V cache rounded to float8 e4m3 (per-row absmax scaling): the
lower-precision control that the correctness limit must reject.
"""

from __future__ import annotations

import zlib
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0

_LAYER_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
_LAYER_NORMS = ("ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias")


def dims(hf: Dict) -> Dict[str, int]:
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    return {
        "L": hf["num_hidden_layers"],
        "D": d,
        "H": h,
        "KV": hf["num_key_value_heads"],
        "HD": d // h,
        "F": hf["intermediate_size"],
        "V": hf["vocab_size"],
    }


def weight_shapes(hf: Dict) -> Dict[str, Tuple[Tuple[int, ...], np.dtype]]:
    """{name: (shape, dtype)}: matrices in the served dtype, norms in
    float32; per-layer arrays are stacked over a leading layer axis."""
    n = dims(hf)
    L, D, H, KV, HD, F, V = (n[k] for k in ("L", "D", "H", "KV", "HD", "F", "V"))
    wdt = jnp.dtype(hf["torch_dtype"])
    out = {
        "embed": ((V, D), wdt),
        "final_norm_scale": ((D,), F32),
        "final_norm_bias": ((D,), F32),
        "wq": ((L, D, H * HD), wdt),
        "wk": ((L, D, KV * HD), wdt),
        "wv": ((L, D, KV * HD), wdt),
        "wo": ((L, H * HD, D), wdt),
        "w_gate": ((L, D, F), wdt),
        "w_up": ((L, D, F), wdt),
        "w_down": ((L, F, D), wdt),
    }
    for name in _LAYER_NORMS:
        out[name] = ((L, D), F32)
    if not hf["tie_word_embeddings"]:
        out["lm_head"] = ((V, D), wdt)
    return out


def seed_words(seed: int) -> Tuple[np.uint32, np.uint32]:
    """A seed of up to 64 bits as two uint32 words (traced, so one compiled
    generator serves every seed)."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def _value(name: str, z: jnp.ndarray, shape: Tuple[int, ...]) -> jnp.ndarray:
    """Scale a standard normal draw for leaf ``name``."""
    if name.endswith("_scale"):
        return 1.0 + 0.1 * z
    if name.endswith("_bias"):
        return 0.1 * z
    if name == "embed":
        return z
    if name == "lm_head":
        return z * shape[-1] ** -0.5
    return z * shape[-2] ** -0.5  # (L, fan_in, fan_out)


def make_weights(hf: Dict) -> Callable:
    """``gen(lo, hi) -> {name: array}``, one jitted call from the seed words
    (:func:`seed_words`) to every weight on the device."""
    shapes = weight_shapes(hf)

    def gen(lo, hi):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo), hi)
        out = {}
        for name, (shape, dtype) in shapes.items():
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            out[name] = _value(name, jax.random.normal(k, shape, F32), shape).astype(dtype)
        return out

    return jax.jit(gen)


# --------------------------------------------------------------------------
# Forward pass
# --------------------------------------------------------------------------


def _identity(x, axis):
    return x


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one absmax scale per slice along
    ``axis`` (the contraction axis of the product it feeds)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(F32) * scale


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x, rot, theta):
    """Rotate-half rotary embedding on the first ``rot`` dims of each head.
    x: (B, T, heads, HD) at positions 0..T-1."""
    if rot == 0:
        return x
    t = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv  # (T, rot/2)
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[None, :, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[None, :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2 :]
    rotated = jnp.concatenate([-x2, x1], -1)
    return jnp.concatenate([xr * cos + rotated * sin, xp], -1)


class Forward:
    """The decoder as three jitted pieces (embedding, one layer, head), so
    that the caller can stream the layers' weights one at a time."""

    def __init__(self, hf: Dict, quant: Callable = _identity):
        n = dims(hf)
        self.n = n
        eps = hf["layer_norm_eps"]
        rot = int(n["HD"] * hf["partial_rotary_factor"])
        theta = float(hf["rope_theta"])
        H, KV, HD = n["H"], n["KV"], n["HD"]

        def mm(x, w):
            # x (..., in) @ w (in, out)
            return jnp.einsum("...i,io->...o", quant(x, -1), quant(w.astype(F32), 0),
                              precision=HIGHEST)

        def embed(table, tokens):
            return table.astype(F32)[tokens]

        def layer(x, w):
            b, t, _ = x.shape
            h = _layer_norm(x, w["ln1_scale"], w["ln1_bias"], eps)
            q = mm(h, w["wq"]).reshape(b, t, H, HD)
            k = mm(h, w["wk"]).reshape(b, t, KV, HD)
            v = mm(h, w["wv"]).reshape(b, t, KV, HD)
            q, k = _rope(q, rot, theta), _rope(k, rot, theta)
            k, v = quant(k, -1), quant(v, -1)  # the K/V cache
            g = H // KV
            qg = q.reshape(b, t, KV, g, HD)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, precision=HIGHEST) * HD**-0.5
            causal = jnp.tril(jnp.ones((t, t), bool))
            s = jnp.where(causal, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v, precision=HIGHEST)
            x = x + mm(o.reshape(b, t, H * HD), w["wo"])
            h = _layer_norm(x, w["ln2_scale"], w["ln2_bias"], eps)
            a = jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"])
            return x + mm(a, w["w_down"])

        def head(x, scale, bias, table):
            h = _layer_norm(x, scale, bias, eps)
            return jnp.einsum("btd,vd->btv", quant(h, -1), quant(table.astype(F32), 1),
                              precision=HIGHEST)

        self._embed = jax.jit(embed)
        self._layer = jax.jit(layer)
        self._head = jax.jit(head)
        self.tied = hf["tie_word_embeddings"]

    def logits(self, w: Dict, tokens: jnp.ndarray) -> jnp.ndarray:
        """(B, T) token ids at positions 0..T-1 -> (B, T, V) float32."""
        x = self._embed(w["embed"], tokens)
        for i in range(self.n["L"]):
            x = self._layer(x, {k: w[k][i] for k in _LAYER_MATRICES + _LAYER_NORMS})
        table = w["embed"] if self.tied else w["lm_head"]
        return self._head(x, w["final_norm_scale"], w["final_norm_bias"], table)


def make_reference(hf: Dict) -> Forward:
    return Forward(hf)


def make_control(hf: Dict) -> Forward:
    return Forward(hf, quant=_fp8)


@jax.jit
def _gap_of(logits, chosen):
    best = jnp.max(logits, -1)
    return best - jnp.take_along_axis(logits, chosen[..., None], -1)[..., 0]


def served_gaps(ref: Forward, w: Dict, tokens: np.ndarray) -> np.ndarray:
    """For sessions ``tokens`` (B, T+1) -- a prompt token, then the tokens
    that the program served -- the gap by which each served token's
    reference logit lies below the reference's best, shape (B, T)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    logits = ref.logits(w, tokens[:, :-1])
    return np.asarray(_gap_of(logits, tokens[:, 1:]))


def control_gaps(ref: Forward, control: Forward, w: Dict, tokens: np.ndarray) -> np.ndarray:
    """The same gap for the token that ``control`` puts first at each
    position of the same sessions (teacher-forced), shape (B, T)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    ref_logits = ref.logits(w, tokens[:, :-1])
    chosen = jnp.argmax(control.logits(w, tokens[:, :-1]), -1).astype(jnp.int32)
    return np.asarray(_gap_of(ref_logits, chosen))
