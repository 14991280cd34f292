"""Plain float32 reference of OLMo training: weights, data, loss, AdamW.

Written from the published description (arXiv:2402.00838; the HF
``OLMo-1B-hf`` config) and from the benchmark's configuration file, with
nothing taken from the program under test:

* weights drawn from the run's seed in the layout the configuration file
  states (``init`` below: truncated normal, scale 0.02 for the tied
  embedding and 1/sqrt(fan_in) for projections, stored in bfloat16);
* batches drawn from the run's seed by the synthetic Zipf + Markov law
  the traffic file states;
* pre-norm blocks with non-parametric LayerNorm, RoPE on interleaved
  pairs, causal softmax attention, SwiGLU MLP, tied unembedding and mean
  next-token cross-entropy;
* AdamW with global-norm clipping on a float32 master copy, whose
  bfloat16 rounding is what the forward pass sees, as the configuration
  states (bf16 parameters, f32 master weights).

Every matrix product runs at ``highest`` precision.  ``precision="fp8"``
is the control: every matrix product's operands rounded to float8 e4m3,
and the gradients flowing back into them to e5m2, each with a per-tensor
scale.  The gradient is accumulated one row of
the batch at a time, and attention and the loss run in chunks, so that
the whole reference fits one chip once the program's state is freed.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, NamedTuple, Sequence

import numpy as np

Q_CHUNK = 512           # query rows per attention chunk
LOSS_CHUNK = 512        # positions per cross-entropy chunk


class Sizes(NamedTuple):
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    theta: float
    eps: float

    @classmethod
    def of(cls, cfg: Dict[str, Any]) -> "Sizes":
        h = int(cfg["num_attention_heads"])
        return cls(d=int(cfg["hidden_size"]), heads=h,
                   kv_heads=int(cfg["num_key_value_heads"]),
                   head_dim=int(cfg["hidden_size"]) // h,
                   ff=int(cfg["intermediate_size"]),
                   vocab=int(cfg["vocab_size"]),
                   layers=int(cfg["num_hidden_layers"]),
                   theta=float(cfg["rope_theta"]),
                   eps=float(cfg["layer_norm_eps"]))


# --------------------------------------------------------------------------- #
# Data                                                                        #
# --------------------------------------------------------------------------- #

def batch(seed: int, step: int, batch_size: int, seq_len: int, vocab: int,
          zipf_a: float, repeat_p: float) -> Dict[str, np.ndarray]:
    """Zipf unigrams; with probability ``repeat_p`` a token is the previous
    one plus 1.  Inputs and next-token labels, (batch, seq) int32."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, 0]))
    base = rng.zipf(zipf_a, size=(batch_size, seq_len + 1))
    tok = (base - 1) % vocab
    rep = rng.random((batch_size, seq_len)) < repeat_p
    tok[:, 1:] = np.where(rep, (tok[:, :-1] + 1) % vocab, tok[:, 1:])
    return {"tokens": tok[:, :-1].astype(np.int32),
            "labels": tok[:, 1:].astype(np.int32)}


# --------------------------------------------------------------------------- #
# Weights                                                                     #
# --------------------------------------------------------------------------- #

def init(seed: int, s: Sizes):
    """bfloat16 weights from the seed: {'tok', 'wq', ...}; per-layer leaves
    are stacked over the layer axis."""
    import jax
    import jax.numpy as jnp

    def tn(key, shape, scale):
        return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
                * scale).astype(jnp.bfloat16)

    def layer(key):
        _, k_attn, _, k_mlp, _, _ = jax.random.split(key, 6)
        kq, kk, kv, ko = jax.random.split(k_attn, 4)
        k_up, k_down, k_gate = jax.random.split(k_mlp, 3)
        r = 1.0 / math.sqrt(s.d)
        return {
            "wq": tn(kq, (s.d, s.heads, s.head_dim), r),
            "wk": tn(kk, (s.d, s.kv_heads, s.head_dim), r),
            "wv": tn(kv, (s.d, s.kv_heads, s.head_dim), r),
            "wo": tn(ko, (s.heads, s.head_dim, s.d),
                     1.0 / math.sqrt(s.heads * s.head_dim)),
            "w_up": tn(k_up, (s.d, s.ff), r),
            "w_down": tn(k_down, (s.ff, s.d), 1.0 / math.sqrt(s.ff)),
            "w_gate": tn(k_gate, (s.d, s.ff), r),
        }

    def make(key):
        k_emb, k_blocks, _, _, _ = jax.random.split(key, 5)
        k_tok, _ = jax.random.split(k_emb)
        w = jax.vmap(layer)(jax.random.split(k_blocks, s.layers))
        w["tok"] = tn(k_tok, (s.vocab, s.d), 0.02)
        return w

    return jax.jit(make)(jax.random.key(seed))


# --------------------------------------------------------------------------- #
# Forward and loss                                                            #
# --------------------------------------------------------------------------- #

def _round(x, dtype):
    """Round to a float8 type with a per-tensor scale, back to float32."""
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _fp8_operand():
    """Identity whose value is rounded to e4m3 and whose gradient to e5m2,
    each with a per-tensor scale: the usual float8 training recipe."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def q(x):
        return _round(x, jnp.float8_e4m3fn)

    def fwd(x):
        return q(x), None

    def bwd(_, g):
        return (_round(g, jnp.float8_e5m2),)

    q.defvjp(fwd, bwd)
    return q


def _mm(spec: str, a, b, fp8: bool):
    import jax.numpy as jnp

    if fp8:
        q = _fp8_operand()
        a, b = q(a), q(b)
    return jnp.einsum(spec, a, b)


def _ln(x, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _rope(x, theta):
    """x: (heads, seq, head_dim); rotates pairs (2i, 2i+1) by pos*theta^(-2i/D)."""
    import jax.numpy as jnp

    h, n, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(h, n, d)


def _block(x, w, s: Sizes, fp8: bool):
    """One layer on one row: x (seq, d) float32."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    h = _ln(x, s.eps)
    q = _rope(_mm("sd,dhk->hsk", h, w["wq"], fp8), s.theta)
    k = _rope(_mm("sd,dhk->hsk", h, w["wk"], fp8), s.theta)
    v = _mm("sd,dhk->hsk", h, w["wv"], fp8)
    rep = s.heads // s.kv_heads
    k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
    qc = min(Q_CHUNK, n)
    scale = 1.0 / math.sqrt(s.head_dim)

    @jax.checkpoint
    def chunk(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qc, qc, axis=1)
        sc = _mm("hqk,htk->hqt", qi, k, fp8) * scale
        pos = i * qc + jnp.arange(qc)
        sc = jnp.where(pos[:, None] >= jnp.arange(n)[None, :], sc, -jnp.inf)
        return _mm("hqt,htk->hqk", jax.nn.softmax(sc, axis=-1), v, fp8)

    ctx = jax.lax.map(chunk, jnp.arange(n // qc))         # (nc, H, qc, hd)
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(s.heads, n, s.head_dim)
    x = x + _mm("hsk,hkd->sd", ctx, w["wo"], fp8)
    h = _ln(x, s.eps)
    g = _mm("sd,df->sf", h, w["w_gate"], fp8)
    u = _mm("sd,df->sf", h, w["w_up"], fp8)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, w["w_down"], fp8)


def _as_served(master):
    """The bfloat16 parameters the forward pass sees, in float32."""
    import jax.numpy as jnp

    return master.astype(jnp.bfloat16).astype(jnp.float32)


def row_nll_sum(master, tokens, labels, s: Sizes, fp8: bool):
    """Sum of next-token negative log-likelihoods over one row."""
    import jax
    import jax.numpy as jnp

    tok = _as_served(master["tok"])
    x = tok[tokens]
    layers = {k: v for k, v in master.items() if k != "tok"}

    @jax.checkpoint
    def body(x, wl):
        return _block(x, jax.tree.map(_as_served, wl), s, fp8), None

    x, _ = jax.lax.scan(body, x, layers)
    x = _ln(x, s.eps)
    n = x.shape[0]
    lc = min(LOSS_CHUNK, n)

    @jax.checkpoint
    def chunk(i):
        xi = jax.lax.dynamic_slice_in_dim(x, i * lc, lc, axis=0)
        li = jax.lax.dynamic_slice_in_dim(labels, i * lc, lc, axis=0)
        logits = _mm("sd,vd->sv", xi, tok, fp8)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    return jnp.sum(jax.lax.map(chunk, jnp.arange(n // lc)))


# --------------------------------------------------------------------------- #
# Training                                                                    #
# --------------------------------------------------------------------------- #

class Readings(NamedTuple):
    losses: List[float]                 # loss of each step, before its update
    grad_norms: Dict[str, float]        # first step's clipped gradient, per leaf
    change_norms: Dict[str, float]      # |master after the steps - initial|


def train(cfg: Dict[str, Any], data: Dict[str, Any], seed: int,
          n_steps: int, *, precision: str = "f32",
          rows: Sequence[int] | None = None) -> Readings:
    """Run ``n_steps`` AdamW steps from the seed and return the readings.

    ``rows`` restricts every batch to those rows (the mean is then taken
    over them): the fault of a step that leaves part of its batch out.
    """
    import jax
    import jax.numpy as jnp

    s = Sizes.of(cfg)
    run = cfg["run"]
    opt = run["optimizer"]
    fp8 = precision == "fp8"
    if precision not in ("f32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    b1, b2, eps = float(opt["b1"]), float(opt["b2"]), float(opt["eps"])
    lr, wd, clip = float(opt["lr"]), float(opt["weight_decay"]), \
        float(opt["grad_clip"])
    bsz, seq = int(run["global_batch"]), int(run["seq_len"])
    rows = list(range(bsz)) if rows is None else list(rows)
    denom = float(len(rows) * seq)

    with jax.default_matmul_precision("highest"):
        master = jax.tree.map(lambda w: w.astype(jnp.float32), init(seed, s))
        m = jax.tree.map(jnp.zeros_like, master)
        v = jax.tree.map(jnp.zeros_like, master)

        @jax.jit
        def row_grad(master, tokens, labels):
            return jax.value_and_grad(row_nll_sum)(master, tokens, labels,
                                                   s, fp8)

        @partial(jax.jit, donate_argnums=(0,))
        def accumulate(acc, g):
            return jax.tree.map(jnp.add, acc, g)

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def update(master, m, v, g, t):
            g = jax.tree.map(lambda x: x / denom, g)
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                 for x in jax.tree.leaves(g)))
            c = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
            g = jax.tree.map(lambda x: x * c, g)
            norms = jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), g)
            m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
            v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
            bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
            master = jax.tree.map(
                lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + eps)
                                          + wd * p), master, m, v)
            return master, m, v, norms

        losses: List[float] = []
        grad_norms: Dict[str, float] = {}
        for step in range(n_steps):
            bt = batch(seed, step, bsz, seq, s.vocab, float(data["zipf_a"]),
                       float(data["repeat_p"]))
            acc, nll = None, 0.0
            for r in rows:
                val, g = row_grad(master, bt["tokens"][r], bt["labels"][r])
                nll += float(val)
                acc = g if acc is None else accumulate(acc, g)
                del g
            losses.append(nll / denom)
            master, m, v, norms = update(master, m, v, acc,
                                         jnp.float32(step + 1))
            del acc
            if step == 0:
                grad_norms = {k: float(x) for k, x in norms.items()}
        del m, v
        # The initial weights again, from the seed, rather than a second
        # copy held on the chip all along.
        moved = jax.jit(lambda p, w0: jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b.astype(jnp.float32)))),
            p, w0))(master, init(seed, s))
        change = {k: float(x) for k, x in moved.items()}
    return Readings(losses=losses, grad_norms=grad_norms, change_norms=change)
