"""Plain reference of Zamba2-7B-Instruct (arXiv:2411.15242; HF
``modeling_zamba2``), for serving.

One full forward pass over a whole sequence in ``jax.numpy`` float32 at
``highest`` matmul precision, with no kernel, no chunked scan and no
cache; it imports nothing of the program under test. ``k`` counts the
hybrid layers of ``hybrid_layer_ids`` below ``num_hidden_layers``, and
block ``b = k mod num_mem_blocks`` is used at the k-th:

    mamba layer l:   h <- h + Mamba2_l(RMSNorm_l(h))
    hybrid layer l:  u = RMSNorm^in_b([h ; e])                # 2d wide
                     a = W^o_b Attn(RoPE(W^q_b u), RoPE(W^k_b u), W^v_b u)
                     a = RMSNorm^ff_b(a)
                     [g ; v] = W^gu_b a + B_k A_k a           # use k's LoRA
                     t = Linear_k(W^down_b (gelu(g) * v))     # erf GELU
                     h <- h + Mamba2_l(RMSNorm_l(h + t))
    logits = RMSNorm_f(h) E^T                                 # tied

``e`` is the embedding of the tokens (what enters layer 0). Attention is
causal, 32 heads of 224 with as many key/value heads, RoPE theta 1e4 on
the whole head, scale ``(224 / 2) ** -0.5``. The Mamba-2 mixer: the
input projection split into z, xBC and dt; the causal depthwise
convolution with bias and SiLU; the SSD recurrence in its quadratic
(masked, attention-like) form
``y_t = sum_{s<=t} C_t.B_s exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t``,
with heads ``g*56 .. g*56+55`` reading group g's B and C; the gated RMSNorm
``rmsnorm(y * silu(z))`` over each group's 3584 channels on their own;
the output projection. The quadratic form and attention are computed in
blocks of query positions, so that 4,608 positions fit on one chip.

Departures from HF, none of which changes the function: matrices are
stored input-major (``x @ W``); the convolution weight is ``(width,
channels)``; the rotary embedding rotates halves of each head
(``rotate_half``), and ``to_program`` permutes each head's q and k
channels to the program's adjacent pairs, which leaves every q.k
unchanged.

It also makes the weights from the seed in bfloat16, the published dtype
(one jitted call; read upcast to float32 here) and lays them out as the
program holds them (``to_program``, which asserts every width).

``low`` selects the control: every matmul operand rounded, with one
scale per tensor, through a lower precision (``"fp8"``: float8_e4m3fn,
the step below the program's bfloat16 compute).
"""
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 256          # query positions per block of the quadratic forms


def dims(cfg):
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    P, G = cfg["mamba_headdim"], cfg["mamba_ngroups"]
    L = cfg["num_hidden_layers"]
    hyb = tuple(i for i in cfg["hybrid_layer_ids"] if i < L)
    return tuple(dict(
        L=L, d=d, di=di, N=cfg["mamba_d_state"], P=P, H=di // P, G=G,
        W=cfg["mamba_d_conv"], V=cfg["vocab_size"], hyb=hyb,
        nb=cfg["num_mem_blocks"], r=cfg["adapter_rank"],
        A=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"],
        Dh=cfg["attention_head_dim"], ff=cfg["ffn_hidden_size"],
        theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        dt_min=cfg["time_step_min"], dt_max=cfg["time_step_max"]).items())


def make_weights(key, cfg):
    """Published-layout bfloat16 weights from ``key``."""
    m = dict(dims(cfg))
    L, d, di, N, H, G, W, V = (m[k] for k in
                               ("L", "d", "di", "N", "H", "G", "W", "V"))
    nb, U, r, A, KV, Dh, ff = (m["nb"], len(m["hyb"]), m["r"], m["A"],
                               m["KV"], m["Dh"], m["ff"])
    conv, proj = di + 2 * G * N, 2 * di + 2 * G * N + H
    ks = iter(jax.random.split(key, 32))
    bf = jnp.bfloat16

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(bf)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    dt = jnp.exp(uniform((L, H), jnp.log(m["dt_min"]), jnp.log(m["dt_max"])))
    return {
        "embed": normal((V, d), d ** -0.5),
        "final_norm": (1.0 + normal((d,), 0.1)).astype(bf),
        "ln": (1.0 + normal((L, d), 0.1)).astype(bf),
        "in_proj": normal((L, d, proj), d ** -0.5),
        "conv_w": normal((L, W, conv), 0.5),
        "conv_b": normal((L, conv), 0.1),
        "A_log": jnp.log(uniform((L, H), 1.0, 16.0)).astype(bf),
        "D": uniform((L, H), 0.5, 1.5).astype(bf),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(bf),
        "norm": (1.0 + normal((L, di), 0.1)).astype(bf),
        "out_proj": normal((L, di, d), di ** -0.5),
        "block": {
            "ln_in": (1.0 + normal((nb, 2 * d), 0.1)).astype(bf),
            "q": normal((nb, 2 * d, A * Dh), (2 * d) ** -0.5),
            "k": normal((nb, 2 * d, KV * Dh), (2 * d) ** -0.5),
            "v": normal((nb, 2 * d, KV * Dh), (2 * d) ** -0.5),
            "o": normal((nb, A * Dh, d), (A * Dh) ** -0.5),
            "ln_ff": (1.0 + normal((nb, d), 0.1)).astype(bf),
            "gate_up": normal((nb, d, 2 * ff), d ** -0.5),
            "down": normal((nb, ff, d), ff ** -0.5),
        },
        "use": {
            "adapter_a": normal((U, d, r), d ** -0.5),
            "adapter_b": normal((U, r, 2 * ff), r ** -0.5),
            "linear": normal((U, d, d), d ** -0.5),
        },
    }


def to_program(p, cfg):
    """The program's parameter tree holding the same function (its output
    matrix is a copy of the embedding's transpose); every width checked
    against the configuration."""
    m = dict(dims(cfg))
    L, d, di, N, H, G, W, V = (m[k] for k in
                               ("L", "d", "di", "N", "H", "G", "W", "V"))
    nb, U, r, A, KV, Dh, ff = (m["nb"], len(m["hyb"]), m["r"], m["A"],
                               m["KV"], m["Dh"], m["ff"])
    conv, proj = di + 2 * G * N, 2 * di + 2 * G * N + H
    want = {"embed": (V, d), "in_proj": (L, d, proj), "conv_w": (L, W, conv),
            "conv_b": (L, conv), "A_log": (L, H), "norm": (L, di),
            "out_proj": (L, di, d), "ln": (L, d)}
    for k, s in want.items():
        assert p[k].shape == s, (k, p[k].shape, s)
    b, u = p["block"], p["use"]
    want = {"ln_in": (nb, 2 * d), "q": (nb, 2 * d, A * Dh),
            "k": (nb, 2 * d, KV * Dh), "v": (nb, 2 * d, KV * Dh),
            "o": (nb, A * Dh, d), "ln_ff": (nb, d),
            "gate_up": (nb, d, 2 * ff), "down": (nb, ff, d)}
    for k, s in want.items():
        assert b[k].shape == s, (k, b[k].shape, s)
    want = {"adapter_a": (U, d, r), "adapter_b": (U, r, 2 * ff),
            "linear": (U, d, d)}
    for k, s in want.items():
        assert u[k].shape == s, (k, u[k].shape, s)
    # HF rotates halves of a head; the program rotates adjacent pairs
    perm = jnp.stack([jnp.arange(Dh // 2), jnp.arange(Dh // 2) + Dh // 2],
                     -1).reshape(Dh)

    def heads(w, n, rotary):
        w = w.reshape(nb, 2 * d, n, Dh)
        return w[..., perm] if rotary else w

    ssm = {k: p[k] for k in ("in_proj", "conv_w", "conv_b", "A_log", "D",
                             "dt_bias", "norm", "out_proj")}
    return {
        "embed": {"tok": p["embed"], "out": p["embed"].T,
                  "final_norm": p["final_norm"]},
        "layers": {"ssm": ssm, "ln": p["ln"]},
        "shared": {
            "ln_in": b["ln_in"],
            "attn": {"wq": heads(b["q"], A, True),
                     "wk": heads(b["k"], KV, True),
                     "wv": heads(b["v"], KV, False),
                     "wo": b["o"].reshape(nb, A, Dh, d)},
            "ln_ff": b["ln_ff"],
            "mlp": {"w_gate": b["gate_up"][..., :ff],
                    "w_up": b["gate_up"][..., ff:], "w_down": b["down"]},
        },
        "uses": {"linear": u["linear"], "adapter_in": u["adapter_a"],
                 "adapter_gate": u["adapter_b"][..., :ff],
                 "adapter_up": u["adapter_b"][..., ff:]},
    }


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


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _blocks(S):
    """Query blocks: (number, rows per block)."""
    t0 = min(BLOCK, S)
    return -(-S // t0), t0


def _ssd(xs, dt, A, Bm, Cm, D, low):
    """The SSD recurrence's quadratic form, a block of query rows at a
    time. xs (S, H, P); dt (S, H); Bm, Cm (S, G, N)."""
    S, H, P = xs.shape
    G = Bm.shape[1]
    cum = jnp.cumsum(dt * A, axis=0)                            # (S, H)
    nblk, t0 = _blocks(S)
    pad = nblk * t0 - S
    cum_q = jnp.pad(cum, ((0, pad), (0, 0)))
    C_q = jnp.pad(Cm, ((0, pad), (0, 0), (0, 0)))
    s_idx = jnp.arange(S)

    def block(i):
        t = i * t0 + jnp.arange(t0)
        ct = jax.lax.dynamic_slice_in_dim(cum_q, i * t0, t0)      # (t0, H)
        cq = jax.lax.dynamic_slice_in_dim(C_q, i * t0, t0)        # (t0,G,N)
        keep = (s_idx[None] <= t[:, None]) & (t[:, None] < S)     # (t0, S)
        seg = jnp.where(keep[..., None], ct[:, None] - cum[None], -jnp.inf)
        cb = _mm("tgn,sgn->tsg", cq, Bm, low)                     # (t0,S,G)
        cb = jnp.repeat(cb, H // G, axis=2)                       # per head
        scores = cb * jnp.exp(seg) * dt[None]                     # (t0,S,H)
        return _mm("tsh,shp->thp", scores, xs, low)

    y = jax.lax.map(block, jnp.arange(nblk)).reshape(nblk * t0, H, P)[:S]
    return y + xs * D[:, None]


def _rope(x, theta):
    """HF's rotary embedding: rotate the two halves of each head."""
    S, _, Dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]   # (S, Dh/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    half = jnp.concatenate([-x[..., Dh // 2:], x[..., :Dh // 2]], -1)
    return x * cos + half * sin


def _attention(q, k, v, scale):
    """Causal softmax attention, a block of query rows at a time.
    q (S, A, Dh); k, v (S, KV, Dh)."""
    S, A, Dh = q.shape
    k = jnp.repeat(k, A // k.shape[1], axis=1)
    v = jnp.repeat(v, A // v.shape[1], axis=1)
    nblk, t0 = _blocks(S)
    q = jnp.pad(q, ((0, nblk * t0 - S), (0, 0), (0, 0)))
    s_idx = jnp.arange(S)

    def block(i):
        t = i * t0 + jnp.arange(t0)
        qb = jax.lax.dynamic_slice_in_dim(q, i * t0, t0)
        logits = jnp.einsum("thd,shd->hts", qb, k, precision=HIGHEST) * scale
        logits = jnp.where(s_idx[None, None] <= t[None, :, None], logits,
                           -jnp.inf)
        probs = jax.nn.softmax(logits, -1)
        return jnp.einsum("hts,shd->thd", probs, v, precision=HIGHEST)

    return jax.lax.map(block, jnp.arange(nblk)).reshape(
        nblk * t0, A, Dh)[:S]


@functools.partial(jax.jit, static_argnames=("m", "low"))
def _logits(p, tokens, m, low):
    m = dict(m)
    d, di, N, H, P, G, W, V, eps = (m[k] for k in ("d", "di", "N", "H", "P",
                                                   "G", "W", "V", "eps"))
    A, KV, Dh, ff, nb = m["A"], m["KV"], m["Dh"], m["ff"], m["nb"]
    S = tokens.shape[0]

    def mamba(x, lp, t):
        h = _rms(x if t is None else x + t, lp["ln"], eps)
        zxbcdt = _mm("sd,dp->sp", h, lp["in_proj"], low)
        z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * G * N],
                      zxbcdt[:, 2 * di + 2 * G * N:])
        pad = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
        xbc = sum(pad[i:i + S] * lp["conv_w"][i] for i in range(W))
        xbc = jax.nn.silu(xbc + lp["conv_b"])
        xs = xbc[:, :di].reshape(S, H, P)
        Bm = xbc[:, di:di + G * N].reshape(S, G, N)
        Cm = xbc[:, di + G * N:].reshape(S, G, N)
        dt = jax.nn.softplus(dt + lp["dt_bias"])                  # (S, H)
        y = _ssd(xs, dt, -jnp.exp(lp["A_log"]), Bm, Cm, lp["D"], low)
        y = (y.reshape(S, di) * jax.nn.silu(z)).reshape(S, G, di // G)
        y = _rms(y, lp["norm"].reshape(G, di // G), eps).reshape(S, di)
        return x + _mm("si,id->sd", y, lp["out_proj"], low)

    def shared(x, e, k):
        b = _f32(jax.tree.map(lambda a: a[k % nb], p["block"]))
        u = _f32(jax.tree.map(lambda a: a[k], p["use"]))
        h = _rms(jnp.concatenate([x, e], -1), b["ln_in"], eps)
        q = _rope(_mm("sw,wf->sf", h, b["q"], low).reshape(S, A, Dh),
                  m["theta"])
        kk = _rope(_mm("sw,wf->sf", h, b["k"], low).reshape(S, KV, Dh),
                   m["theta"])
        v = _mm("sw,wf->sf", h, b["v"], low).reshape(S, KV, Dh)
        a = _attention(_round(q, low), _round(kk, low), _round(v, low),
                       (Dh / 2) ** -0.5)
        a = _mm("sf,fd->sd", a.reshape(S, A * Dh), b["o"], low)
        a = _rms(a, b["ln_ff"], eps)
        gu = _mm("sd,df->sf", a, b["gate_up"], low) + _mm(
            "sr,rf->sf", _mm("sd,dr->sr", a, u["adapter_a"], low),
            u["adapter_b"], low)
        y = jax.nn.gelu(gu[:, :ff], approximate=False) * gu[:, ff:]
        y = _mm("sf,fd->sd", y, b["down"], low)
        return _mm("sd,de->se", y, u["linear"], low)

    layers = {k: p[k] for k in ("ln", "in_proj", "conv_w", "conv_b", "A_log",
                                "D", "dt_bias", "norm", "out_proj")}
    e = p["embed"][tokens].astype(jnp.float32)
    x, lo = e, 0
    for k, l in enumerate(m["hyb"] + (m["L"],)):
        run = jax.tree.map(lambda a: a[lo:l], layers)
        x, _ = jax.lax.scan(lambda x, lp: (mamba(x, _f32(lp), None), None),
                            x, run)
        if l < m["L"]:
            t = shared(x, e, k)
            x = mamba(x, _f32(jax.tree.map(lambda a: a[l], layers)), t)
        lo = l + 1
    x = _rms(x, p["final_norm"].astype(jnp.float32), eps)
    return _mm("sd,vd->sv", x, p["embed"].astype(jnp.float32), low)


def logits(p, tokens, cfg, low=None):
    """(S, vocab) next-token logits of a whole sequence of token ids."""
    return _logits(p, tokens, dims(cfg), low)
