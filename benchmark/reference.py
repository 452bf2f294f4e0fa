"""The plain reference: the published forward pass in `jax.numpy` and
float32, at `highest` matmul precision, one layer (and one expert) of
weights upcast at a time. Here are the pieces families share, written
from the published descriptions (RMS-norm, rotary embedding in the
half-split convention, grouped-query causal attention, SwiGLU, a router
whose top-k logits are softmaxed over a plain loop over every expert)
and the comparison of served tokens; a family's module
(benchmark/families/) puts them together into its forward pass. It
imports nothing of the program.

`lowp=True` is the control: the same mathematics with both operands of
every matmul rounded to an 8-bit float (e4m3: three bits of mantissa,
per-tensor scale), the nearest precision below the bfloat16 the
configurations state.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import families

F32 = jnp.float32


def fp8_round(x):
    """x rounded to e4m3's grid under a per-tensor scale that puts the
    largest magnitude at 448; straight-through for gradients."""
    x = x.astype(F32)
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    y = x * scale
    exponent = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
    step = jnp.exp2(exponent - 3)
    q = jnp.round(y / step) * step / scale
    return x + jax.lax.stop_gradient(q - x)


def mm(a, b, lowp):
    if lowp:
        a, b = fp8_round(a), fp8_round(b)
    return jnp.matmul(a.astype(F32), b.astype(F32),
                      precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x, positions, theta):
    """x: [T, heads, head_dim]; rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = positions.astype(F32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(q, k, v, lowp):
    """Causal, grouped-query; q: [T, H, hd], k and v: [T, KV, hd]. One
    group of query heads at a time, so that the [T, T] scores of all
    heads never exist together."""
    T, H, hd = q.shape
    KV = k.shape[1]
    q = q.reshape(T, KV, H // KV, hd).transpose(1, 2, 0, 3)  # [KV, G, T, hd]
    k = k.transpose(1, 0, 2)
    v = v.transpose(1, 0, 2)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    @jax.checkpoint
    def group(qkv):
        qg, kg, vg = qkv
        scores = mm(qg, kg.T[None], lowp) * (hd ** -0.5)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return mm(jax.nn.softmax(scores, -1), vg[None], lowp)

    out = jax.lax.map(group, (q, k, v))  # [KV, G, T, hd]
    return out.transpose(2, 0, 1, 3).reshape(T, H * hd)


def swiglu(h, w_gate, w_up, w_down, lowp):
    return mm(jax.nn.silu(mm(h, w_gate, lowp)) * mm(h, w_up, lowp),
              w_down, lowp)


def moe(h, p, top_k, lowp):
    """Every expert computes every token; a token keeps the experts of
    its top-k router logits, weighted by the softmax over those k."""
    logits = mm(h, p["router"], lowp)
    top, idx = jax.lax.top_k(logits, top_k)
    gate = jax.nn.softmax(top, -1)
    n_experts = p["router"].shape[-1]
    weight = jnp.sum(jax.nn.one_hot(idx, n_experts, dtype=F32)
                     * gate[..., None], axis=-2)  # [T, N]

    def one(acc, ew):
        w_gate, w_up, w_down, w = ew
        return acc + w[:, None] * swiglu(h, w_gate, w_up, w_down, lowp), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (p["w_gate"], p["w_up"], p["w_down"], weight.T))
    return out


def logits(params, tokens, dims, lowp=False):
    """Float32 logits [T, vocab] of one sequence of tokens: the plain
    forward of the configuration's family."""
    return families.load(dims["family"]).logits(params, tokens, dims, lowp)


@jax.jit
def _gaps(ref, judged):
    return jnp.max(ref, -1) - jnp.take_along_axis(ref, judged[:, None], -1)[:, 0]


_argmax = jax.jit(lambda x: jnp.argmax(x, -1))


def served_gaps(params, prompt, served, dims, pad_to, control=False):
    """For one finished request: at each served position, how far the
    served token's reference logit lies below the reference's best.
    With `control`, the token judged is the one the lower precision
    puts first at that position of the same prompt and served tokens.
    Every request is padded to `pad_to` (padding follows the tokens and
    the mask is causal, so it is never seen): one shape, one program.
    Returns a numpy array, one gap per served token."""
    tokens = np.zeros(pad_to, np.int32)
    seq = list(prompt) + list(served)
    tokens[:len(seq)] = seq
    ref = logits(params, tokens, dims)
    if control:
        judged = _argmax(logits(params, tokens, dims, lowp=True))
    else:
        judged = jnp.asarray(np.roll(tokens, -1))  # position i predicts i+1
    out = np.asarray(_gaps(ref, judged))
    return out[len(prompt) - 1:len(seq) - 1]
