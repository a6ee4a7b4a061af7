"""Plain building blocks of the benchmark's references: attention, layer
norm, feed-forward, softmax cross-entropy and Adam in `jax.numpy` float32.

Nothing here imports the program under test.  Every matrix product goes
through a `Dots` object so that one reference can be computed in two
precisions:

- `Dots("f32")`: float32 operands, `Precision.HIGHEST` (on a TPU a float32
  product otherwise runs in one bfloat16 pass).  This is the reference.
- `Dots("fp8")`: the operands of every product, forward and backward, are
  rounded to float8_e4m3 under a per-tensor scale (amax / 448) and the
  product accumulates in float32.  This is the CONTROL: the nearest
  precision below the bfloat16 the configurations state, put in the
  program's place; `correct` has to fail it.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _round_fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _swap(x):
    return jnp.swapaxes(x, -1, -2)


def _matmul(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


@jax.custom_vjp
def _mm_fp8(a, w):
    return _matmul(_round_fp8(a), _round_fp8(w))


def _mm_fp8_fwd(a, w):
    return _mm_fp8(a, w), (a, w)


def _mm_fp8_bwd(res, g):
    a, w = res
    gq, aq, wq = _round_fp8(g), _round_fp8(a), _round_fp8(w)
    da = _matmul(gq, wq.T)
    dw = _matmul(aq.reshape(-1, aq.shape[-1]).T, gq.reshape(-1, gq.shape[-1]))
    return da, dw


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


@jax.custom_vjp
def _bmm_fp8(a, b):
    return _matmul(_round_fp8(a), _round_fp8(b))


def _bmm_fp8_fwd(a, b):
    return _bmm_fp8(a, b), (a, b)


def _bmm_fp8_bwd(res, g):
    a, b = res
    gq, aq, bq = _round_fp8(g), _round_fp8(a), _round_fp8(b)
    return _matmul(gq, _swap(bq)), _matmul(_swap(aq), gq)


_bmm_fp8.defvjp(_bmm_fp8_fwd, _bmm_fp8_bwd)


class Dots:
    """The two matrix products a transformer needs, in one precision.

    `mm(a, w)`: activations [..., k] by a weight [k, n].
    `bmm(a, b)`: two operands of equal rank, batched over leading axes."""

    def __init__(self, mode="f32"):
        if mode not in ("f32", "fp8"):
            raise ValueError(f"unknown precision mode {mode!r}")
        self.mode = mode

    def mm(self, a, w):
        return _mm_fp8(a, w) if self.mode == "fp8" else _matmul(a, w)

    def bmm(self, a, b):
        return _bmm_fp8(a, b) if self.mode == "fp8" else _matmul(a, b)


def layer_norm(x, scale, bias, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def split_heads(x, n_head):
    b, t, hd = x.shape
    return x.reshape(b, t, n_head, hd // n_head).transpose(0, 2, 1, 3)


def attention(dots, q, k, v, bias, n_head):
    """q [b,tq,h*d], k and v [b,tk,h*d], bias broadcastable to
    [b,h,tq,tk]; scaled dot-product attention, heads merged on return."""
    d = q.shape[-1] // n_head
    qh, kh, vh = (split_heads(t, n_head) for t in (q, k, v))
    scores = dots.bmm(qh, _swap(kh)) * (d ** -0.5)
    if bias is not None:
        scores = scores + bias
    ctx = dots.bmm(jax.nn.softmax(scores, axis=-1), vh)
    b, h, t, _ = ctx.shape
    return ctx.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def self_attention(dots, x, w_qkv, w_out, bias, n_head):
    """Packed projection [d_model, 3*h*d]: columns are q, then k, then v."""
    q, k, v = jnp.split(dots.mm(x, w_qkv), 3, axis=-1)
    return dots.mm(attention(dots, q, k, v, bias, n_head), w_out)


def cross_attention(dots, x, mem, w_q, w_k, w_v, w_out, bias, n_head):
    ctx = attention(dots, dots.mm(x, w_q), dots.mm(mem, w_k),
                    dots.mm(mem, w_v), bias, n_head)
    return dots.mm(ctx, w_out)


def feed_forward(dots, x, w_in, b_in, w_out, b_out, act):
    return dots.mm(act(dots.mm(x, w_in) + b_in), w_out) + b_out


def weighted_cross_entropy_sum(logits, labels, weights):
    """sum over tokens of weight * (logsumexp(logits) - logits[label])."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - picked) * weights)


def xavier(key, shape):
    fan_in, fan_out = shape[0], shape[-1]
    return jax.random.normal(key, shape, jnp.float32) * (
        2.0 / (fan_in + fan_out)) ** 0.5


def sinusoid_table(n_pos, d_model):
    """Vaswani et al. 2017, section 3.5, times d_model ** -0.5.

    The paper adds the sinusoids to embeddings that it has multiplied by
    sqrt(d_model).  Where a builder leaves that multiplication out, the
    same input divided by sqrt(d_model) keeps word and position in the
    paper's ratio; unscaled sinusoids would be 16 times the embeddings at
    d_model 512, and in bfloat16 their sum would round the words away."""
    pos = jnp.arange(n_pos, dtype=jnp.float32)[:, None]
    i = jnp.arange(d_model // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2.0 * i / d_model)
    table = jnp.stack([jnp.sin(angle), jnp.cos(angle)], axis=-1).reshape(
        n_pos, d_model)
    return table * d_model ** -0.5


def adam_update(p, g, m, v, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Kingma & Ba 2015, algorithm 1 in its `lr_t` form: step `t` counts
    from 1; epsilon is added to sqrt(v), outside the bias correction."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    return p - lr_t * m / (jnp.sqrt(v) + eps), m, v


def make_train_step(loss_sum_fn, weights_field, trainable, rows_per_block):
    """One training step of the reference, computed in blocks of rows.

    `loss_sum_fn(params, block)` is the weighted loss summed over the rows
    of `block`.  The step's loss is that sum over all rows divided by the
    sum of all weights (`batch[weights_field]`), so each block's gradient
    is taken of its share of the quotient and the shares add up.  Only the
    leaves named in `trainable` get gradients and Adam (learning rate `lr`,
    a run-time scalar: at 0 the weights stay and the moments still
    gather); the step also returns the norm of each leaf's gradient."""

    def step(params, m, v, t, lr, batch):
        n_rows = batch[weights_field].shape[0]
        n_blocks = n_rows // rows_per_block
        blocks = {k: x.reshape((n_blocks, rows_per_block) + x.shape[1:])
                  for k, x in batch.items()}
        frozen = {k: x for k, x in params.items() if k not in trainable}
        train_p = {k: params[k] for k in trainable}
        total_w = jnp.sum(batch[weights_field])

        def share(tp, block):
            return loss_sum_fn({**frozen, **tp}, block) / total_w

        def body(acc, block):
            loss, grads = jax.value_and_grad(share)(train_p, block)
            return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], grads)), None

        zero = jax.tree.map(jnp.zeros_like, train_p)
        (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0.0), zero),
                                        blocks)
        new_p, new_m, new_v = dict(params), {}, {}
        for k in trainable:
            new_p[k], new_m[k], new_v[k] = adam_update(
                params[k], grads[k], m[k], v[k], t, lr)
        return loss, new_p, new_m, new_v, leaf_norms(grads)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def make_loss(loss_sum_fn, weights_field, rows_per_block):
    """The step's loss alone, a forward pass in the same blocks of rows."""

    def loss(params, batch):
        n_rows = batch[weights_field].shape[0]
        blocks = {k: x.reshape((n_rows // rows_per_block, rows_per_block)
                               + x.shape[1:]) for k, x in batch.items()}
        total, _ = jax.lax.scan(
            lambda acc, block: (acc + loss_sum_fn(params, block), None),
            jnp.float32(0.0), blocks)
        return total / jnp.sum(batch[weights_field])

    return jax.jit(loss)


def leaf_norms(tree):
    return {k: jnp.linalg.norm(x.ravel().astype(jnp.float32))
            for k, x in tree.items()}


def seed_key(seed):
    """A PRNG key from any whole number: `--seed` may pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_init(leaves):
    """A jitted `init(key)` that makes every leaf of a reference in one
    call on the device, in float32 (the type the master weights are kept
    in); call it with `seed_key(seed)`.

    `leaves` is an ordered list of (name, shape, kind, trainable); kind is
    `xavier`, `normal:<std>`, `ones`, `zeros` or `sinusoid`."""

    def init(key):
        out = {}
        for i, (name, shape, kind, _) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            if kind == "xavier":
                out[name] = xavier(k, shape)
            elif kind.startswith("normal:"):
                std = float(kind.split(":", 1)[1])
                out[name] = jax.random.normal(k, shape, jnp.float32) * std
            elif kind == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "zeros":
                out[name] = jnp.zeros(shape, jnp.float32)
            elif kind == "sinusoid":
                out[name] = sinusoid_table(*shape)
            else:
                raise ValueError(f"unknown init kind {kind!r} for {name}")
        return out

    return jax.jit(init)
