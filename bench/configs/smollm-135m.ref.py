"""Plain reference of SmolLM-135M (the Llama architecture), for training.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
RMSNorm, rotary attention over rotated halves with grouped key/value
heads and a causal mask, a SiLU-gated MLP, an output matrix of its own
(the form the program trains; the published model ties it to the
embedding, and the train runner refuses that configuration) and the
mean cross-entropy, the gradient by ``jax.grad``, and AdamW with global
norm clipping and linear warm-up as the job file states. No kernel,
no cache, no sharding; it imports nothing of the program under test.

It also makes the weights (from the seed, in one jitted call) and lays
them out as the program holds them (``to_program``).

``low`` selects the control: every matmul operand rounded, with one
scale per tensor, through a lower precision (``"fp8"``: float8_e4m3fn,
the step below the program's bfloat16 compute).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg):
    """The sizes the reference needs, as a hashable tuple of pairs."""
    d, H, KV = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    Dh = cfg.get("head_dim") or d // H
    mult = cfg["assumed"]["vocab_padding_multiple"]
    V = cfg["vocab_size"]
    return tuple(dict(
        L=cfg["num_hidden_layers"], d=d, H=H, KV=KV, Dh=Dh,
        ff=cfg["intermediate_size"], V=V, Vp=-(-V // mult) * mult,
        eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"]).items())


def make_weights(key, cfg):
    """Canonical (published-layout) float32 weights from ``key``."""
    m = dict(dims(cfg))
    L, d, H, KV, Dh, ff, Vp = (m[k] for k in
                               ("L", "d", "H", "KV", "Dh", "ff", "Vp"))
    ks = iter(jax.random.split(key, 16))

    def mat(shape, fan_in):
        return jax.random.normal(next(ks), shape, jnp.float32) * fan_in ** -0.5

    def scale(shape):
        return 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)

    return {
        "embed": mat((Vp, d), d),
        "lm_head": mat((d, Vp), d),
        "final_norm": scale((d,)),
        "wq": mat((L, d, H, Dh), d),
        "wk": mat((L, d, KV, Dh), d),
        "wv": mat((L, d, KV, Dh), d),
        "wo": mat((L, H, Dh, d), H * Dh),
        "w_gate": mat((L, d, ff), d),
        "w_up": mat((L, d, ff), d),
        "w_down": mat((L, ff, d), ff),
        "ln1": scale((L, d)),
        "ln2": scale((L, d)),
    }


def _interleave(Dh):
    """Program column j of a query/key head holds canonical column perm[j]:
    the program rotates pairs (2i, 2i+1), the published model (i, i+Dh/2)."""
    half = Dh // 2
    return np.stack([np.arange(half), np.arange(half) + half], 1).reshape(-1)


def to_program(p, cfg):
    """The program's parameter tree holding the same function."""
    perm = _interleave(dict(dims(cfg))["Dh"])
    return {
        "embed": {"tok": p["embed"], "out": p["lm_head"],
                  "final_norm": p["final_norm"]},
        "layers": {
            "attn": {"wq": p["wq"][..., perm], "wk": p["wk"][..., perm],
                     "wv": p["wv"], "wo": p["wo"]},
            "mlp": {"w_up": p["w_up"], "w_down": p["w_down"],
                    "w_gate": p["w_gate"]},
            "ln1": p["ln1"], "ln2": p["ln2"],
        },
    }


# --------------------------------------------------------------------------
def _round(x, low):
    if low is None:
        return x
    if low == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        s = jax.lax.stop_gradient(s)
        q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return x + jax.lax.stop_gradient(q - x)
    if low == "bf16":
        return x + jax.lax.stop_gradient(
            x.astype(jnp.bfloat16).astype(jnp.float32) - x)
    raise ValueError(low)


def _mm(eq, a, b, low):
    return jnp.einsum(eq, _round(a, low), _round(b, low), precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate halves: x is (S, heads, Dh)."""
    S, _, Dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = x[..., :Dh // 2], x[..., Dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def row_loss(p, tokens, labels, m, low=None):
    """Mean next-token cross-entropy of one sequence; ``m`` is ``dims``."""
    m = dict(m)
    H, KV, Dh, V, eps = m["H"], m["KV"], m["Dh"], m["V"], m["eps"]
    S = tokens.shape[0]
    x = p["embed"][tokens]
    mask = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def layer(x, lp):
        h = _rms(x, lp["ln1"], eps)
        q = _rope(_mm("sd,dhk->shk", h, lp["wq"], low), m["theta"])
        k = _rope(_mm("sd,dhk->shk", h, lp["wk"], low), m["theta"])
        v = _mm("sd,dhk->shk", h, lp["wv"], low)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = _mm("qhk,thk->hqt", q, k, low) * Dh ** -0.5
        s = jnp.where(mask[None], s, -jnp.inf)
        a = _mm("hqt,thk->qhk", jax.nn.softmax(s, -1), v, low)
        x = x + _mm("qhk,hkd->qd", a, lp["wo"], low)
        h = _rms(x, lp["ln2"], eps)
        g = jax.nn.silu(_mm("sd,df->sf", h, lp["w_gate"], low)) \
            * _mm("sd,df->sf", h, lp["w_up"], low)
        return x + _mm("sf,fd->sd", g, lp["w_down"], low), None

    layers = {k: p[k] for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up",
                                "w_down", "ln1", "ln2")}
    x, _ = jax.lax.scan(layer, x, layers)
    logits = _mm("sd,dv->sv", _rms(x, p["final_norm"], eps),
                 p["lm_head"][:, :V], low)
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(lse - picked)


@functools.partial(jax.jit, static_argnames=("m", "low", "keep"))
def _batch_grad(p, tokens, labels, m, low, keep):
    """Loss and gradient of the batch mean, a row at a time. Rows past
    ``keep`` are left out (a planted fault: the mean over part of the
    batch)."""
    tokens, labels = tokens[:keep], labels[:keep]

    def body(acc, row):
        l, g = jax.value_and_grad(row_loss)(p, row[0], row[1], m, low)
        return jax.tree.map(jnp.add, acc, (l, g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
    (l, g), _ = jax.lax.scan(body, zero, (tokens, labels))
    return jax.tree.map(lambda a: a / keep, (l, g))



def lr_scale(step, opt):
    """Linear warm-up, then cosine to ``min_ratio``, as the job states."""
    step = float(step)
    w, total = opt["warmup_steps"], opt["total_steps"]
    if step < w:
        return step / max(w, 1)
    prog = min(max((step - w) / max(total - w, 1), 0.0), 1.0)
    return opt["min_ratio"] + (1 - opt["min_ratio"]) * 0.5 * (
        1 + np.cos(np.pi * prog))


def train(p0, batches, cfg, opt, steps, low=None, keep=None):
    """Run ``steps`` AdamW steps from ``p0`` on ``batches`` (a list of
    (tokens, labels) of shape (B, S)). Returns the losses, the first
    step's gradient as the optimizer gets it (after clipping), and the
    parameters after the last step."""
    m = dims(cfg)
    p = p0
    mu = jax.tree.map(jnp.zeros_like, p)
    nu = jax.tree.map(jnp.zeros_like, p)
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]
    losses, first_grad = [], None
    for i in range(steps):
        tokens, labels = batches[i]
        rows = keep or tokens.shape[0]
        loss, g = _batch_grad(p, tokens, labels, m, low, rows)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
        g = jax.tree.map(lambda x: x * clip, g)
        if first_grad is None:
            first_grad = g
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        t = i + 1
        lr = opt["lr"] * lr_scale(i, opt)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(
            lambda w, m, v: w - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)
                                      + opt["weight_decay"] * w), p, mu, nu)
        losses.append(float(loss))
    return losses, first_grad, p
