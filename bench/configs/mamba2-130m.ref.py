"""Plain reference of Mamba-2 130M (arXiv:2405.21060), for serving.

One full forward pass over a whole sequence in ``jax.numpy`` float32 at
``highest`` matmul precision: RMSNorm, the input projection split into
z, xBC and dt, the causal depthwise convolution with SiLU, the SSD
recurrence in its quadratic (masked, attention-like) form
``y_t = sum_{s<=t} C_t.B_s exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t``,
the gated RMSNorm ``rmsnorm(y * silu(z))``, the output projection and the
residual; then the final norm and the output matrix tied to the
embedding. No kernel, no chunking, no state cache; it imports nothing of
the program under test.

It also makes the weights from the seed (one jitted call) and lays them
out as the program holds them (``to_program``).

``low`` selects the control: every matmul operand rounded, with one
scale per tensor, through a lower precision (``"fp8"``: float8_e4m3fn,
the step below the program's bfloat16 compute).
"""
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg):
    s = cfg["ssm_cfg"]
    d = cfg["d_model"]
    di = s["expand"] * d
    N, P, W = s["d_state"], s["headdim"], s["d_conv"]
    mult = cfg["assumed"]["vocab_padding_multiple"]
    rows = cfg.get("pad_vocab_size_multiple", 1)
    V = -(-cfg["vocab_size"] // rows) * rows     # the published model's rows
    return tuple(dict(L=cfg["n_layer"], d=d, di=di, N=N, P=P, H=di // P, W=W,
                      V=V, Vp=-(-V // mult) * mult,
                      eps=cfg["norm_epsilon"]).items())


def make_weights(key, cfg):
    """Published-layout float32 weights from ``key``."""
    m = dict(dims(cfg))
    L, d, di, N, H, W, Vp = (m[k] for k in
                             ("L", "d", "di", "N", "H", "W", "Vp"))
    conv, proj = di + 2 * N, 2 * di + 2 * N + H
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    dt = jnp.exp(uniform((L, H), jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "embed": normal((Vp, d), d ** -0.5),
        "final_norm": 1.0 + normal((d,), 0.1),
        "ln": 1.0 + normal((L, d), 0.1),
        "in_proj": normal((L, d, proj), d ** -0.5),
        "conv_w": normal((L, W, conv), 0.5),
        "conv_b": normal((L, conv), 0.1),
        "A_log": jnp.log(uniform((L, H), 1.0, 16.0)),
        "D": uniform((L, H), 0.5, 1.5),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
        "norm": 1.0 + normal((L, di), 0.1),
        "out_proj": normal((L, di, d), di ** -0.5),
    }


def to_program(p, cfg):
    """The program's parameter tree holding the same function (its output
    matrix is a copy of the embedding's transpose)."""
    ssm = {k: p[k] for k in ("in_proj", "conv_w", "conv_b", "A_log", "D",
                             "dt_bias", "norm", "out_proj")}
    return {"embed": {"tok": p["embed"], "out": p["embed"].T,
                      "final_norm": p["final_norm"]},
            "layers": {"ssm": ssm, "ln": p["ln"]}}


def _round(x, low):
    if low is None:
        return x
    if low == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    if low == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    raise ValueError(low)


def _mm(eq, a, b, low):
    return jnp.einsum(eq, _round(a, low), _round(b, low), precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


@functools.partial(jax.jit, static_argnames=("m", "low"))
def _logits(p, tokens, m, low):
    m = dict(m)
    di, N, H, P, W, V, eps = (m[k] for k in
                              ("di", "N", "H", "P", "W", "V", "eps"))
    S = tokens.shape[0]
    tri = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        h = _rms(x, lp["ln"], eps)
        zxbcdt = _mm("sd,dp->sp", h, lp["in_proj"], low)
        z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * N],
                      zxbcdt[:, 2 * di + 2 * N:])
        pad = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
        xbc = sum(pad[i:i + S] * lp["conv_w"][i] for i in range(W))
        xbc = jax.nn.silu(xbc + lp["conv_b"])
        xs = xbc[:, :di].reshape(S, H, P)
        Bm, Cm = xbc[:, di:di + N], xbc[:, di + N:]
        dt = jax.nn.softplus(dt + lp["dt_bias"])               # (S, H)
        cum = jnp.cumsum(dt * -jnp.exp(lp["A_log"]), axis=0)    # (S, H)
        seg = jnp.where(tri[..., None], cum[:, None] - cum[None], -jnp.inf)
        scores = _mm("tn,sn->ts", Cm, Bm, low)[..., None] * jnp.exp(seg) \
            * dt[None]                                          # (t, s, H)
        y = _mm("tsh,shp->thp", scores, xs, low) + xs * lp["D"][:, None]
        y = y.reshape(S, di) * jax.nn.silu(z)
        y = _rms(y, lp["norm"], eps)
        return x + _mm("si,id->sd", y, lp["out_proj"], low), None

    layers = {k: p[k] for k in ("ln", "in_proj", "conv_w", "conv_b", "A_log",
                                "D", "dt_bias", "norm", "out_proj")}
    x, _ = jax.lax.scan(layer, p["embed"][tokens], layers)
    x = _rms(x, p["final_norm"], eps)
    return _mm("sd,vd->sv", x, p["embed"][:V], low)


def logits(p, tokens, cfg, low=None):
    """(S, vocab) next-token logits of a whole sequence of token ids."""
    return _logits(p, tokens, dims(cfg), low)
