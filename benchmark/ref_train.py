"""The plain reference for training: it follows the program's first steps
from the same seed and the same batches, in float32 at `highest`
precision, and reports what the comparison reads: each step's loss, the
norm of the first gradient of every leaf as the optimizer gets it
(after the clip by the global norm), and the norm of every leaf's change
after the steps.

The optimizer is written from its published description (Shazeer and
Stern 2018, as the traffic file states it: a clip by the global norm,
a factored second moment with decay 1 - t^-0.8 and no update clipping,
an absolute learning rate on a linear warm-up and cosine decay, a first
moment with no bias correction, decoupled weight decay). Parameters and
the first moment are stored in the types the configuration states
(bfloat16) and every operation is float32: a float32 master copy would
be another training run, not this one in a higher precision.

Memory: one layer's float32 gradient at a time. The backward pass runs
twice a step, once for the norms that the clip needs and once to apply
the update, so that the gradients are never held whole.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import families, weights

F32 = jnp.float32
EPS = 1e-30
DECAY_EXPONENT = 0.8
MIN_DIM_TO_FACTOR = 128


def learning_rate(opt, t):
    """Linear from 0 to `lr` over `warmup_steps`, then a cosine to a
    tenth of it at `total_steps`; t counts from 0."""
    warm, peak = opt["warmup_steps"], opt["lr"]
    if t < warm:
        return peak * t / warm
    span = max(opt["total_steps"], warm + 1) - warm
    frac = min(1.0, (t - warm) / span)
    return 0.1 * peak + 0.9 * peak * 0.5 * (1 + math.cos(math.pi * frac))


def factored(shape):
    return len(shape) >= 2 and sorted(shape)[-2] >= MIN_DIM_TO_FACTOR


def _sumsq(x):
    return jnp.sum(jnp.square(x.astype(F32)))


def _moments(g):
    """What the second moment keeps of one gradient leaf (one layer's
    slice): row and column means of its square, or the square."""
    sq = jnp.square(g)
    if factored(g.shape):
        return {"row": jnp.mean(sq, -1), "col": jnp.mean(sq, -2)}
    return {"full": sq}


def _new_moment(v, m, clip, beta2):
    return jax.tree.map(
        lambda old, new: beta2 * old + (1 - beta2) * (clip * clip * new + EPS),
        v, m)


def _apply(p, mom, g, v, clip, lr, opt):
    """One leaf's update: scaled gradient, learning rate, first moment,
    decay; stored back in the leaf's own types."""
    g = g * clip
    if "row" in v:
        row = (v["row"] / jnp.mean(v["row"], -1, keepdims=True)) ** -0.5
        u = g * row[..., :, None] * (v["col"] ** -0.5)[..., None, :]
    else:
        u = g * v["full"] ** -0.5
    m = opt["b1"] * mom.astype(F32) + (1 - opt["b1"]) * lr * u
    p32 = p.astype(F32)
    new = p32 - m - lr * opt["weight_decay"] * p32
    return new.astype(p.dtype), m.astype(mom.dtype)


def training_family(dims):
    """The family's module, where it exports the `layer` and `head` that
    the follower differentiates (one stack of `layers` between `embed`
    and `final_norm`, `lm_head`); a family without them has no training
    reference."""
    family = families.load(dims["family"])
    if not (hasattr(family, "layer") and hasattr(family, "head")):
        raise NotImplementedError(
            "family %r exports no `layer` and `head`: it has no training "
            "reference" % (dims["family"],))
    return family


class Follower(object):
    def __init__(self, key, dims, opt, lowp=False):
        family = training_family(dims)
        self.opt = opt
        stacked = jax.jit(lambda k: weights.init_params(k, dims))(key)
        L = dims["n_layers"]
        self.top = {k: v for k, v in stacked.items() if k != "layers"}
        self.layers = [jax.tree.map(lambda a: a[i], stacked["layers"])
                       for i in range(L)]
        del stacked
        mom_dtype = jnp.dtype(opt["momentum_dtype"])
        zeros = lambda tree: jax.tree.map(
            lambda a: jnp.zeros(a.shape, mom_dtype), tree)
        self.mom_top, self.mom_layers = zeros(self.top), \
            [zeros(l) for l in self.layers]
        self.v_top = self.v_layers = None
        self.t = 0
        d, lp = dims, lowp

        def fwd(p, x):
            return jax.vmap(lambda xs: family.layer(p, xs, d, lp))(x)

        def f32(tree):
            return jax.tree.map(lambda a: a.astype(F32), tree)

        def layer_grads(p, x, dx):
            _, vjp = jax.vjp(fwd, f32(p), x)
            return vjp(dx)

        def stats_of(g):
            return jax.tree.map(_sumsq, g), jax.tree.map(_moments, g)

        def bwd_stats(p, x, dx):
            g, dx = layer_grads(p, x, dx)
            return stats_of(g), dx

        def apply_all(p, mom, g, v, clip, lr):
            new = {k: _apply(p[k], mom[k], g[k], v[k], clip, lr, opt)
                   for k in p}
            return ({k: n[0] for k, n in new.items()},
                    {k: n[1] for k, n in new.items()})

        def bwd_apply(p, mom, x, dx, v, clip, lr):
            g, dx = layer_grads(p, x, dx)
            return apply_all(p, mom, g, v, clip, lr) + (dx,)

        def loss_of(top, x, targets):
            logits = family.head(x, top["final_norm"], top["lm_head"], d, lp)
            lse = jax.nn.logsumexp(logits, -1)
            picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
            return jnp.mean(lse - picked)

        def top_grads(top, x, targets):
            """Loss, the head's gradients, and the cotangent of the last
            hidden state."""
            loss, (g, dx) = jax.value_and_grad(loss_of, argnums=(0, 1))(
                f32({k: top[k] for k in ("final_norm", "lm_head")}),
                x, targets)
            return loss, g, dx

        def embed_grad(embed, tokens, dx0):
            return jnp.zeros(embed.shape, F32).at[tokens].add(dx0)

        self._fwd = jax.jit(fwd)
        self._bwd_stats = jax.jit(bwd_stats)
        self._bwd_apply = jax.jit(bwd_apply, donate_argnums=(0, 1))
        self._top_grads = jax.jit(top_grads)
        self._embed_grad = jax.jit(embed_grad)
        self._stats = jax.jit(stats_of)
        self._apply_all = jax.jit(apply_all, donate_argnums=(0, 1))

    def _forward(self, tokens):
        inputs = jnp.asarray(tokens[:, :-1])
        xs = [self.top["embed"][inputs].astype(F32)]
        for p in self.layers:
            xs.append(self._fwd(p, xs[-1]))
        return inputs, jnp.asarray(tokens[:, 1:]), xs

    def step(self, tokens):
        """One training step on `tokens` [B, S+1]. Returns the loss and
        every leaf's clipped gradient norm."""
        opt, t = self.opt, self.t
        inputs, targets, xs = self._forward(tokens)
        # pass 1: the norms and what the second moment keeps
        loss, g_head, dx_last = self._top_grads(self.top, xs[-1], targets)
        sq_top, mo_top = self._stats(g_head)
        sq_layers, mo_layers = [], []
        dx = dx_last
        for i in reversed(range(len(self.layers))):
            (sq, mo), dx = self._bwd_stats(self.layers[i], xs[i], dx)
            sq_layers.append(sq)
            mo_layers.append(mo)
        sq_layers.reverse()
        mo_layers.reverse()
        g_embed = self._embed_grad(self.top["embed"], inputs, dx)
        sq_e, mo_e = self._stats({"embed": g_embed})
        sq_top.update(sq_e)
        mo_top.update(mo_e)
        sumsq = {(k,): float(v) for k, v in sq_top.items()}
        for name in sq_layers[0]:
            sumsq[("layers", name)] = float(sum(s[name] for s in sq_layers))
        norm = math.sqrt(sum(sumsq.values()))
        clip = min(1.0, opt["clip_norm"] / norm)
        grad_norm = {k: clip * math.sqrt(v) for k, v in sumsq.items()}
        beta2 = 1.0 - (t + 1.0) ** -DECAY_EXPONENT
        if self.v_top is None:
            zero = lambda tree: jax.tree.map(jnp.zeros_like, tree)
            self.v_top, self.v_layers = zero(mo_top), \
                [zero(m) for m in mo_layers]
        self.v_top = _new_moment(self.v_top, mo_top, clip, beta2)
        self.v_layers = [_new_moment(v, m, clip, beta2)
                         for v, m in zip(self.v_layers, mo_layers)]
        del mo_top, mo_layers
        lr = learning_rate(opt, t)
        if lr > 0:
            # pass 2: the same gradients again, applied layer by layer
            g_top = dict(g_head, embed=g_embed)
            self.top, self.mom_top = self._apply_all(
                self.top, self.mom_top, g_top, self.v_top, clip, lr)
            del g_top
            dx = dx_last
            for i in reversed(range(len(self.layers))):
                self.layers[i], self.mom_layers[i], dx = self._bwd_apply(
                    self.layers[i], self.mom_layers[i], xs[i], dx,
                    self.v_layers[i], clip, lr)
        del g_head, g_embed, xs
        self.t += 1
        return float(loss), grad_norm

    def leaf(self, path):
        if path[0] == "layers":
            return jnp.stack([l[path[1]] for l in self.layers])
        return self.top[path[0]]

    def moment_slices(self):
        """The first rows of every leaf's first moment (see
        `moment_slice`), layers stacked, as float32 on the host."""
        out = {(k,): moment_slice(v) for k, v in self.mom_top.items()}
        for name in self.mom_layers[0]:
            out[("layers", name)] = np.stack(
                [moment_slice(l[name]) for l in self.mom_layers])
        return out


SLICE_ROWS = 64
# the first moment is read after the second step: the first step's
# learning rate is 0 on a warm-up from 0, so that moment is the second
# batch's scaled gradient at the seeded weights, element by element
MOMENT_AFTER_STEP = 2


def moment_slice(m):
    """The first rows of a leaf (of each layer's matrix), whole vectors:
    small enough to keep on the host while the window runs."""
    m = m if m.ndim < 2 else m[..., :SLICE_ROWS, :]
    return np.asarray(m.astype(F32))


def whole_diff(got, want):
    """The norm of the difference over all leaves together, against the
    reference's norm over all of them: steadier from seed to seed than
    the worst leaf, which a leaf with few large entries can swing."""
    diff = sum(float(np.sum(np.square(got[k] - want[k]))) for k in want)
    norm = sum(float(np.sum(np.square(v))) for v in want.values())
    return math.sqrt(diff / norm)


def worst_leaf_diff(got, want):
    """The largest norm of a leaf's difference, against the reference's
    norm of that leaf or of the median leaf, whichever is larger. Unlike
    a gap between two norms it sees an error that has no bias."""
    norm = {k: float(np.linalg.norm(v)) for k, v in want.items()}
    floor = float(np.median(list(norm.values())))
    return max(float(np.linalg.norm(got[k] - want[k])) / max(norm[k], floor)
               for k in want)


def delta_norms(get_leaf, key, dims):
    """Norm of each leaf's change from the seeded weights, which are
    drawn again leaf by leaf. get_leaf(path) gives the leaf as it is
    now, layers stacked."""
    fn = jax.jit(lambda now, k, path: jnp.sqrt(_sumsq(
        now.astype(F32) - weights.make_leaf(k, dims, path).astype(F32))),
        static_argnums=2)
    return {path: float(fn(get_leaf(path), key, path))
            for path in weights.leaf_specs(dims)}


def follow(key, dims, opt, batches, lowp=False):
    """The readings of the first len(batches) steps."""
    ref = Follower(key, dims, opt, lowp=lowp)
    losses, first, moment = [], None, None
    for i, tokens in enumerate(batches):
        loss, grad_norm = ref.step(np.asarray(tokens))
        losses.append(loss)
        first = first or grad_norm
        if i == MOMENT_AFTER_STEP - 1:
            moment = ref.moment_slices()
    return {"loss": losses, "grad_norm": first, "moment": moment,
            "delta_norm": delta_norms(ref.leaf, key, dims)}


def worst_leaf_gap(got, want):
    """The largest gap between two norms of one leaf, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger: some leaves' norms are all but zero."""
    floor = float(np.median(list(want.values())))
    return max(abs(got[k] - want[k]) / max(want[k], floor) for k in want)
