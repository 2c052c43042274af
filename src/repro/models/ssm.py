"""Mamba2 (SSD) block: projections + causal depthwise conv + selective state
space scan, with O(1)-state decode. [arXiv:2405.21060]
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import layers as L


def conv_dim(cfg: ModelConfig) -> int:
    return cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state


def proj_dim(cfg: ModelConfig) -> int:
    # [z (d_inner) | xBC (d_inner + 2GN) | dt (H)]
    return 2 * cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state \
        + cfg.ssm_heads


def ssm_params(key, cfg: ModelConfig, layers: Optional[int] = None,
               dtype=jnp.float32):
    d = cfg.d_model
    H = cfg.ssm_heads
    ks = L.split_keys(key, 4)
    lead = () if layers is None else (layers,)

    def mk(k, shape, fan_in):
        if layers is None:
            return L.dense_init(k, shape, fan_in, dtype)
        return jax.vmap(lambda kk: L.dense_init(kk, shape, fan_in, dtype))(
            jax.random.split(k, layers))

    # A in [1, 16) as in mamba2 init; dt_bias ~ softplus^-1(dt) left at zeros
    a_init = jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32))
    return {
        "in_proj": mk(ks[0], (d, proj_dim(cfg)), d),
        "conv_w": jnp.zeros(lead + (cfg.d_conv, conv_dim(cfg)), dtype)
        + (1.0 / cfg.d_conv),
        "conv_b": jnp.zeros(lead + (conv_dim(cfg),), dtype),
        "A_log": jnp.broadcast_to(a_init, lead + (H,)).astype(dtype),
        "D": jnp.ones(lead + (H,), dtype),
        "dt_bias": jnp.zeros(lead + (H,), dtype),
        "norm": jnp.ones(lead + (cfg.d_inner,), dtype),
        "out_proj": mk(ks[3], (cfg.d_inner, d), cfg.d_inner),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array] = None):
    """Depthwise causal conv. x: (B, S, Cd); w: (W, Cd).

    With ``state`` ((B, W-1, Cd), decode history) returns (y, new_state).
    """
    W = w.shape[0]
    if state is not None:
        xin = jnp.concatenate([state, x], axis=1)           # (B, W-1+S, Cd)
        new_state = xin[:, -(W - 1):, :]
    else:
        xin = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
        new_state = None
    y = sum(xin[:, i:i + x.shape[1], :] * w[i][None, None, :] for i in range(W))
    y = y + b[None, None, :]
    return jax.nn.silu(y), new_state


def _split_proj(zxbcdt, cfg: ModelConfig):
    di, GN = cfg.d_inner, cfg.ssm_groups * cfg.ssm_state
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:2 * di + 2 * GN]
    dt = zxbcdt[..., 2 * di + 2 * GN:]
    return z, xBC, dt


def _split_bc(xBC, cfg: ModelConfig):
    """B and C out of the convolved xBC: ``(..., N)`` for one group, else
    ``(..., G, N)``."""
    di, G, N = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    Bm = xBC[..., di:di + G * N]
    Cm = xBC[..., di + G * N:]
    if G > 1:
        Bm = Bm.reshape(*Bm.shape[:-1], G, N)
        Cm = Cm.reshape(*Cm.shape[:-1], G, N)
    return Bm, Cm


def ssm_block(
    x: jax.Array,                 # (B, S, d)
    p: dict,
    cfg: ModelConfig,
    *,
    compute_dtype=jnp.bfloat16,
    ssd_impl: str = "auto",
    state=None,                   # decode: {"conv": (B,W-1,Cd), "ssd": (B,H,P,N)}
    return_state: bool = False,   # prefill: sequence mode + final decode state
):
    """Returns (out, new_state) — new_state None unless ``state`` given or
    ``return_state`` (prefill: sequence-mode outputs plus the conv/ssd state
    a subsequent ``decode_step`` continues from).

    With ``cfg.ssm_groups`` G > 1, heads ``g*H/G .. (g+1)*H/G - 1`` read
    group g's B and C, and the gated norm normalises each group's
    ``d_inner / G`` channels on their own (Mamba-2's ``ngroups``). One
    group is the plain Mamba-2 mixer."""
    cd = compute_dtype
    B_, S, _ = x.shape
    H, N, P, G = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim, cfg.ssm_groups
    di = cfg.d_inner

    zxbcdt = jnp.einsum("bsd,dp->bsp", x.astype(cd), p["in_proj"].astype(cd))
    z, xBC, dt = _split_proj(zxbcdt, cfg)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    new_state = None
    if state is None:
        if return_state:
            # zero conv state == the zero-padding of the stateless path, so
            # outputs are bit-identical AND we get the final conv history.
            zero = jnp.zeros((B_, p["conv_w"].shape[0] - 1, xBC.shape[-1]),
                             jnp.bfloat16)
            xBC, conv_state = _causal_conv(xBC, p["conv_w"].astype(cd),
                                           p["conv_b"].astype(cd), zero)
        else:
            xBC, conv_state = _causal_conv(xBC, p["conv_w"].astype(cd),
                                           p["conv_b"].astype(cd))
        xs = xBC[..., :di].reshape(B_, S, H, P)
        Bm, Cm = _split_bc(xBC, cfg)
        y = ops.ssd(xs, dt, A, Bm, Cm, p["D"].astype(jnp.float32),
                    chunk=min(cfg.ssm_chunk, S), impl=ssd_impl)
        y = y.reshape(B_, S, di)
        if return_state:
            # closed form of the decode recurrence
            #   state_t = state_{t-1} * exp(dt_t A) + dt_t B_t (x) x_t
            # after S steps: state_S = sum_t exp(A (D_S - D_t)) dt_t B_t x_t
            # with D the inclusive cumsum of dt.
            cum = jnp.cumsum(dt, axis=1)                       # (B,S,H)
            decay = jnp.exp((cum[:, -1:] - cum) * A[None, None, :])
            if G == 1:
                ssd_state = jnp.einsum(
                    "bsh,bsn,bshp->bhpn", dt * decay,
                    Bm.astype(jnp.float32), xs.astype(jnp.float32))
            else:
                ssd_state = jnp.einsum(
                    "bsgh,bsgn,bsghp->bghpn",
                    (dt * decay).reshape(B_, S, G, H // G),
                    Bm.astype(jnp.float32),
                    xs.astype(jnp.float32).reshape(B_, S, G, H // G, P),
                ).reshape(B_, H, P, N)
            new_state = {"conv": conv_state, "ssd": ssd_state}
    else:
        xBC, conv_state = _causal_conv(xBC, p["conv_w"].astype(cd),
                                       p["conv_b"].astype(cd), state["conv"])
        xs = xBC[..., :di].reshape(B_, S, H, P)[:, 0]        # (B,H,P)
        if G == 1:
            Bm = xBC[:, 0, di:di + N]                        # (B,N)
            Cm = xBC[:, 0, di + N:]
        else:
            Bm, Cm = _split_bc(xBC[:, 0], cfg)               # (B,G,N)
        dt0 = dt[:, 0]                                       # (B,H)
        a = jnp.exp(dt0 * A[None, :])                        # (B,H)
        if G == 1:
            upd = jnp.einsum("bh,bn,bhp->bhpn", dt0, Bm.astype(jnp.float32),
                             xs.astype(jnp.float32))
            ssd_state = state["ssd"] * a[..., None, None] + upd
            y = jnp.einsum("bn,bhpn->bhp", Cm.astype(jnp.float32), ssd_state)
        else:   # each head reads its group's B and C
            Bh = jnp.repeat(Bm.astype(jnp.float32), H // G, axis=1)
            Ch = jnp.repeat(Cm.astype(jnp.float32), H // G, axis=1)
            upd = jnp.einsum("bh,bhn,bhp->bhpn", dt0, Bh,
                             xs.astype(jnp.float32))
            ssd_state = state["ssd"] * a[..., None, None] + upd
            y = jnp.einsum("bhn,bhpn->bhp", Ch, ssd_state)
        y = y + xs.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, :, None]
        y = y.reshape(B_, 1, di).astype(cd)
        new_state = {"conv": conv_state, "ssd": ssd_state}

    # gated RMSNorm (per group) then out-projection
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    if G == 1:
        y = L.rms_norm(y.astype(cd), p["norm"], cfg.norm_eps)
    else:
        y = L.rms_norm(y.astype(cd).reshape(B_, S, G, di // G),
                       p["norm"].reshape(G, di // G),
                       cfg.norm_eps).reshape(B_, S, di)
    out = jnp.einsum("bsi,id->bsd", y.astype(cd), p["out_proj"].astype(cd))
    return out.astype(x.dtype), new_state


def init_ssm_state(cfg: ModelConfig, batch: int, layers: int,
                   dtype=jnp.float32):
    """Per layer: the conv history and the SSD state, ``(heads, head_dim,
    state)`` with the state size (128 in mamba2) last: a TPU tiles it
    without padding and updates and reads it in that layout, where a
    head-dim-last state is relaid out twice per layer each decode step."""
    return {
        "conv": jnp.zeros((layers, batch, cfg.d_conv - 1, conv_dim(cfg)),
                          jnp.bfloat16),
        "ssd": jnp.zeros((layers, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                          cfg.ssm_state), dtype),
    }


# ---------------------------------------------------------------------------
# full mamba2 model (cfg.family == "ssm")
# ---------------------------------------------------------------------------
def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    ke, kl = L.split_keys(key, 2)
    return {
        "embed": L.embed_params(ke, cfg, dtype),
        "layers": {
            "ssm": ssm_params(kl, cfg, layers=cfg.num_layers, dtype=dtype),
            "ln": jnp.ones((cfg.num_layers, cfg.d_model), dtype),
        },
    }


def forward(params, embeds, cfg: ModelConfig, *, compute_dtype=jnp.bfloat16,
            ssd_impl="auto", remat: bool = False, unroll: bool = False):
    from repro.parallel.sharding import constrain_residual

    def body(x, lp):
        h = L.rms_norm(x, lp["ln"], cfg.norm_eps)
        y, _ = ssm_block(h, lp["ssm"], cfg, compute_dtype=compute_dtype,
                         ssd_impl=ssd_impl)
        return constrain_residual(x + y), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = L.layer_scan(body, embeds, params["layers"], unroll=unroll)
    return x


def loss_fn(params, batch, cfg: ModelConfig, *, compute_dtype=jnp.bfloat16,
            ssd_impl="auto", remat=False, unroll=False, loss_chunk=512, **_):
    from repro.models import transformer as T
    x = T.embed_tokens(params, batch["tokens"], cfg, compute_dtype)
    h = forward(params, x, cfg, compute_dtype=compute_dtype,
                ssd_impl=ssd_impl, remat=remat, unroll=unroll)
    loss = L.lm_head_loss(h, params["embed"], batch["labels"], cfg,
                          compute_dtype=compute_dtype, chunk=loss_chunk)
    return loss, {}


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16):
    del cache_len  # O(1) state — the whole point of running long_500k on SSMs
    return init_ssm_state(cfg, batch, cfg.num_layers)


# decode_step in its three parts; a serving engine that keeps every
# request's state stacked by layer runs the loop over layers itself
# (``ModelAPI.layer_decode``)
def decode_embed(params, tokens, cfg: ModelConfig, *,
                 compute_dtype=jnp.bfloat16, **_):
    from repro.models import transformer as T
    return T.embed_tokens(params, tokens, cfg, compute_dtype)


def _decode_layer(lp, x, state, cfg: ModelConfig, compute_dtype):
    h = L.rms_norm(x, lp["ln"], cfg.norm_eps)
    y, ns = ssm_block(h, lp["ssm"], cfg, compute_dtype=compute_dtype,
                      state=state)
    return x + y, ns


def decode_layer(params, l, x, state, cfg: ModelConfig, *,
                 compute_dtype=jnp.bfloat16, **_):
    """Layer ``l`` of :func:`decode_step`, ``state`` its ``{"conv",
    "ssd"}``: the new hidden and the layer's new state."""
    lp = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False),
        params["layers"])
    return _decode_layer(lp, x, state, cfg, compute_dtype)


def decode_head(params, x, cfg: ModelConfig, *, compute_dtype=jnp.bfloat16,
                **_):
    from repro.models import transformer as T
    return T.logits_fn(params, x, cfg, compute_dtype)[:, 0]


def decode_step(params, cache, tokens, cfg: ModelConfig, *,
                compute_dtype=jnp.bfloat16, unroll: bool = False, **_):
    x = decode_embed(params, tokens, cfg, compute_dtype=compute_dtype)

    def body(x, xs):
        lp, conv, ssd_st = xs
        x, ns = _decode_layer(lp, x, {"conv": conv, "ssd": ssd_st}, cfg,
                              compute_dtype)
        return x, (ns["conv"], ns["ssd"])

    x, (nc, nss) = L.layer_scan(
        body, x, (params["layers"], cache["conv"], cache["ssd"]),
        unroll=unroll)
    logits = decode_head(params, x, cfg, compute_dtype=compute_dtype)
    return logits, {"conv": nc, "ssd": nss}


def prefill(params, tokens, cfg: ModelConfig, cache_len: int, *,
            compute_dtype=jnp.bfloat16, ssd_impl="auto",
            unroll: bool = False, **_):
    """Run the prompt in sequence mode, returning (logits, decode state)."""
    from repro.models import transformer as T
    del cache_len  # O(1) state
    x = T.embed_tokens(params, tokens, cfg, compute_dtype)

    def body(x, lp):
        h = L.rms_norm(x, lp["ln"], cfg.norm_eps)
        y, ns = ssm_block(h, lp["ssm"], cfg, compute_dtype=compute_dtype,
                          ssd_impl=ssd_impl, return_state=True)
        return x + y, (ns["conv"], ns["ssd"])

    x, (nc, nss) = L.layer_scan(body, x, params["layers"], unroll=unroll)
    logits = T.logits_fn(params, x, cfg, compute_dtype)
    return logits, {"conv": nc, "ssd": nss}
