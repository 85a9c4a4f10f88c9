"""xLSTM blocks [arXiv:2405.04517]: mLSTM (matrix memory, chunkwise-parallel
with stabilized exponential gating) and sLSTM (scalar memory, sequential
recurrence with block-diagonal hidden-to-hidden weights).

TPU adaptation: the mLSTM forward uses the chunkwise form — per-chunk
quadratic (attention-like) compute plus a carried (C, n, m) state — which maps
onto the MXU, instead of the CUDA fused recurrent kernel. The value/feature
dimension is tensor-parallel over ``model`` ("inner" logical axis); q/k and the
normalizer are replicated (they are the small, hash-join-broadcast side).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax import shard_map
from repro.core.config import ModelConfig, XLSTMConfig
from repro.models.layers import _init
from repro.models.ssm import _causal_conv
from repro.parallel.sharding import logical_shard

Params = dict
Axes = dict


def _dims(cfg: ModelConfig) -> tuple[int, int, int, int, int]:
    x = cfg.xlstm or XLSTMConfig()
    d_in = int(x.proj_factor * cfg.d_model)
    h = cfg.num_heads
    qk = int(x.qk_dim_factor * d_in)
    return d_in, h, qk, qk // h, d_in // h      # d_in, H, qk, dk, dv


def _headnorm(h: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head RMS norm. h: (..., H, dv); scale: (H*dv,)."""
    h32 = h.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(jnp.square(h32), axis=-1, keepdims=True) + eps)
    out = (h32 * rms).reshape(*h.shape[:-2], -1)
    return (out * scale.astype(jnp.float32)).astype(scale.dtype)


# =========================== mLSTM =============================================


def init_mlstm(cfg: ModelConfig, key) -> tuple[Params, Axes]:
    d = cfg.d_model
    d_in, h, qk, _, _ = _dims(cfg)
    x = cfg.xlstm or XLSTMConfig()
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 6)
    params: Params = {
        "up": _init(keys[0], (d, 2 * d_in), d ** -0.5, dtype),
        "conv_w": _init(keys[1], (x.conv_kernel, d_in), 0.3, dtype),
        "conv_b": jnp.zeros((d_in,), dtype),
        "wq": _init(keys[2], (d_in, qk), d_in ** -0.5, dtype),
        "wk": _init(keys[3], (d_in, qk), d_in ** -0.5, dtype),
        "wv": _init(keys[4], (d_in, d_in), d_in ** -0.5, dtype),
        "w_if": _init(keys[5], (d_in, 2 * h), d_in ** -0.5, jnp.float32),
        # forget-gate bias init in [3, 6] keeps early training stable (paper).
        "b_if": jnp.concatenate(
            [jnp.zeros((h,)), jnp.linspace(3.0, 6.0, h)]).astype(jnp.float32),
        "norm": jnp.ones((d_in,), dtype),
        "down": _init(jax.random.fold_in(key, 7), (d_in, d), d_in ** -0.5,
                      dtype),
    }
    axes: Axes = {
        "up": ("w_embed", "inner"),
        "conv_w": (None, "inner"),
        "conv_b": ("inner",),
        "wq": ("inner", None),
        "wk": ("inner", None),
        "wv": ("inner", "inner"),
        "w_if": ("inner", None),
        "b_if": (None,),
        "norm": ("inner",),
        "down": ("inner", "w_embed"),
    }
    return params, axes


def _mlstm_qkv_gates(params: Params, x: jax.Array, cfg: ModelConfig,
                     conv_state=None):
    """Shared pre-processing. x: (B,S,D) -> q,k,v,(log_i,log_f),z,state."""
    d_in, h, qk, dk, dv = _dims(cfg)
    uz = jnp.einsum("bsd,de->bse", x, params["up"])
    uz = logical_shard(uz, "batch", "seq", "inner")
    u, z = jnp.split(uz, 2, axis=-1)
    c, conv_state = _causal_conv(u, params["conv_w"], params["conv_b"],
                                 conv_state)
    c = jax.nn.silu(c.astype(jnp.float32)).astype(x.dtype)
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dk->bsk", c, params["wq"]).reshape(b, s, h, dk)
    k = jnp.einsum("bsd,dk->bsk", c, params["wk"]).reshape(b, s, h, dk)
    v = jnp.einsum("bsd,de->bse", u, params["wv"]).reshape(b, s, h, dv)
    v = logical_shard(v, "batch", "seq", None, "inner")
    gates = jnp.einsum("bsd,dg->bsg", c.astype(jnp.float32), params["w_if"])
    gates = gates + params["b_if"]
    log_i, raw_f = jnp.split(gates.reshape(b, s, 2, h), 2, axis=2)
    log_f = jax.nn.log_sigmoid(raw_f[:, :, 0])          # (B,S,H)
    log_i = log_i[:, :, 0]
    k = k * (dk ** -0.5)
    return q, k, v, log_i, log_f, z, conv_state


def mlstm(params: Params, x: jax.Array, cfg: ModelConfig,
          chunk: int = 256, return_state: bool = False):
    """Chunkwise-parallel mLSTM forward. x: (B,S,D)."""
    b, s, _ = x.shape
    d_in, h, qk, dk, dv = _dims(cfg)
    q, k, v, log_i, log_f, z, conv_tail = _mlstm_qkv_gates(params, x, cfg)

    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    def split(t, time_axis=1):  # (B,S,...) -> (nc,B,chunk,...)
        t = t.reshape(*t.shape[:time_axis], nc, chunk, *t.shape[time_axis + 1:])
        return jnp.moveaxis(t, time_axis, 0)

    def step(carry, inputs):
        c_mat, n_vec, m = carry            # (B,H,dk,dv), (B,H,dk), (B,H)
        qc, kc, vc, lic, lfc = inputs      # (B,C,H,*)
        lic = jnp.moveaxis(lic, 1, 2)      # (B,H,C)
        lfc = jnp.moveaxis(lfc, 1, 2)
        f_cum = jnp.cumsum(lfc, axis=-1)   # F_t
        g = lic - f_cum                    # g_s = li_s - F_s
        m_running = jax.lax.cummax(g, axis=2)      # (B,H,C)
        mx = jnp.maximum(m[..., None], m_running)
        m_t = f_cum + mx                   # new stabilizer per position
        alpha = jnp.exp(m[..., None] - mx)             # inter-chunk scale
        w = jnp.exp(g[:, :, None, :] - mx[..., None])  # (B,H,t,s)
        t_idx = jnp.arange(chunk)
        causal = t_idx[:, None] >= t_idx[None, :]
        w = jnp.where(causal[None, None], w, 0.0)

        qf = jnp.moveaxis(qc, 1, 2).astype(jnp.float32)  # (B,H,C,dk)
        kf = jnp.moveaxis(kc, 1, 2).astype(jnp.float32)
        vf = jnp.moveaxis(vc, 1, 2).astype(jnp.float32)  # (B,H,C,dv)
        # pin the value/feature dim sharding through the scan body —
        # without these the partitioner flip-flops between dv- and H-
        # sharded layouts and inserts full rematerializations (§Perf H3)
        vf = logical_shard(vf, "batch", None, None, "inner")

        scores = jnp.einsum("bhtk,bhsk->bhts", qf, kf) * w
        num = jnp.einsum("bhts,bhsv->bhtv", scores, vf) \
            + alpha[..., None] * jnp.einsum("bhtk,bhkv->bhtv", qf, c_mat)
        num = logical_shard(num, "batch", None, None, "inner")
        n_t = jnp.einsum("bhts,bhsk->bhtk", w, kf) \
            + alpha[..., None] * n_vec[:, :, None]
        den = jnp.maximum(
            jnp.abs(jnp.einsum("bhtk,bhtk->bht", qf, n_t)), jnp.exp(-m_t))
        h_out = num / den[..., None]       # (B,H,C,dv)

        # carry update at t = chunk end
        w_last = jnp.exp(g - mx[..., -1:])             # (B,H,C)
        c_new = alpha[..., -1, None, None] * c_mat \
            + jnp.einsum("bhs,bhsk,bhsv->bhkv", w_last, kf, vf)
        c_new = logical_shard(c_new, "batch", None, None, "inner")
        n_new = n_t[:, :, -1]
        m_new = m_t[..., -1]
        return (c_new, n_new, m_new), jnp.moveaxis(h_out, 1, 2)  # (B,C,H,dv)

    carry0 = (
        jnp.zeros((b, h, dk, dv), jnp.float32),
        jnp.zeros((b, h, dk), jnp.float32),
        jnp.full((b, h), -1e9, jnp.float32),
    )
    carry, h_chunks = jax.lax.scan(
        step, carry0,
        (split(q), split(k), split(v), split(log_i), split(log_f)))
    h_all = jnp.moveaxis(h_chunks, 0, 1).reshape(b, s, h, dv)

    y = _headnorm(h_all, params["norm"])
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    y = logical_shard(y, "batch", "seq", "inner")
    out = jnp.einsum("bsd,de->bse", y, params["down"])
    out = logical_shard(out, "batch", "seq", "embed")
    if return_state:
        return out, {"c": carry[0], "n": carry[1], "m": carry[2],
                     "conv": conv_tail}
    return out


def init_mlstm_state(cfg: ModelConfig, batch: int) -> dict:
    d_in, h, qk, dk, dv = _dims(cfg)
    x = cfg.xlstm or XLSTMConfig()
    return {
        "c": jnp.zeros((batch, h, dk, dv), jnp.float32),
        "n": jnp.zeros((batch, h, dk), jnp.float32),
        "m": jnp.full((batch, h), -1e9, jnp.float32),
        "conv": jnp.zeros((batch, x.conv_kernel - 1, d_in),
                          jnp.dtype(cfg.dtype)),
    }


def mlstm_state_axes() -> dict:
    return {"c": ("batch", None, None, "inner"),
            "n": ("batch", None, None),
            "m": ("batch", None),
            "conv": ("batch", None, "inner")}


def mlstm_step(params: Params, state: dict, x: jax.Array,
               cfg: ModelConfig) -> tuple[jax.Array, dict]:
    """One decode step. x: (B,1,D)."""
    q, k, v, log_i, log_f, z, conv_state = _mlstm_qkv_gates(
        params, x, cfg, state["conv"])
    qf = q[:, 0].astype(jnp.float32)       # (B,H,dk)
    kf = k[:, 0].astype(jnp.float32)
    vf = v[:, 0].astype(jnp.float32)       # (B,H,dv)
    li, lf = log_i[:, 0], log_f[:, 0]      # (B,H)

    m_new = jnp.maximum(lf + state["m"], li)
    f_sc = jnp.exp(lf + state["m"] - m_new)
    i_sc = jnp.exp(li - m_new)
    c_new = f_sc[..., None, None] * state["c"] \
        + i_sc[..., None, None] * kf[..., :, None] * vf[..., None, :]
    c_new = logical_shard(c_new, "batch", None, None, "inner")
    n_new = f_sc[..., None] * state["n"] + i_sc[..., None] * kf
    num = jnp.einsum("bhk,bhkv->bhv", qf, c_new)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", qf, n_new)),
                      jnp.exp(-m_new))
    h_out = (num / den[..., None])[:, None]          # (B,1,H,dv)

    y = _headnorm(h_out, params["norm"])
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    out = jnp.einsum("bsd,de->bse", y, params["down"])
    out = logical_shard(out, "batch", "seq", "embed")
    return out, {"c": c_new, "n": n_new, "m": m_new, "conv": conv_state}


# =========================== sLSTM =============================================


def init_slstm(cfg: ModelConfig, key) -> tuple[Params, Axes]:
    d = cfg.d_model
    d_in, h, _, _, dv = _dims(cfg)
    x = cfg.xlstm or XLSTMConfig()
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 4)
    params: Params = {
        "up": _init(keys[0], (d, 2 * d_in), d ** -0.5, dtype),
        "conv_w": _init(keys[1], (x.conv_kernel, d_in), 0.3, dtype),
        "conv_b": jnp.zeros((d_in,), dtype),
        "w_gates": _init(keys[2], (d_in, 4 * d_in), d_in ** -0.5, dtype),
        "r_gates": _init(keys[3], (4, h, dv, dv), dv ** -0.5, jnp.float32),
        "b_gates": jnp.concatenate([
            jnp.zeros((2 * d_in,)),                     # z, i
            jnp.full((d_in,), 3.0),                     # f bias
            jnp.zeros((d_in,)),                         # o
        ]).astype(jnp.float32),
        "norm": jnp.ones((d_in,), dtype),
        "down": _init(jax.random.fold_in(key, 5), (d_in, d), d_in ** -0.5,
                      dtype),
    }
    axes: Axes = {
        "up": ("w_embed", "inner"),
        "conv_w": (None, "inner"),
        "conv_b": ("inner",),
        "w_gates": ("inner", "inner"),
        "r_gates": (None, None, None, None),
        "b_gates": (None,),
        "norm": ("inner",),
        "down": ("inner", "w_embed"),
    }
    return params, axes


def _slstm_scan(params: Params, gates_x: jax.Array, h: int, dv: int,
                state: dict):
    """Sequential recurrence. gates_x: (B,S,4*d_in) precomputed input part.

    Wrapped in shard_map over the batch axes when a mesh is active: under
    plain GSPMD the backward pass all-reduces the recurrent-weight gradient
    at EVERY timestep (64 MiB x seq_len x layers — the dominant xlstm wire,
    §Perf H3); inside shard_map the local dR accumulates through the scan
    and is psummed once at the boundary.
    """
    from repro.parallel.sharding import current_rules

    rules = current_rules()
    r = params["r_gates"]                  # (4,H,dv,dv)
    if rules is not None and rules.mesh is not None \
            and rules.rules.get("batch") is not None:
        from jax.sharding import PartitionSpec as P

        b_ax = rules.rules["batch"]
        bspec3 = P(b_ax, None, None)
        bspec2 = P(b_ax, None)
        state_specs = {k: bspec3 if v.ndim == 3 else bspec2
                       for k, v in state.items() if k != "conv"}
        st = {k: v for k, v in state.items() if k != "conv"}
        fn = shard_map(
            lambda r_, gx_, st_: _slstm_scan_body(r_, gx_, h, dv, st_),
            mesh=rules.mesh,
            in_specs=(P(None, None, None, None), bspec3, state_specs),
            out_specs=(bspec3, (bspec2,) * 4),
            check_vma=False)
        hs, carry = fn(r, gates_x, st)
        return hs, carry
    st = {k: v for k, v in state.items() if k != "conv"}
    return _slstm_scan_body(r, gates_x, h, dv, st)


def _slstm_scan_body(r: jax.Array, gates_x: jax.Array, h: int, dv: int,
                     state: dict):
    def step(carry, gx):
        c, n, hid, m = carry               # (B,d_in) each
        hid_heads = hid.reshape(hid.shape[0], h, dv)
        rec = jnp.einsum("bhv,ghvw->gbhw", hid_heads, r)
        rec = rec.reshape(4, hid.shape[0], h * dv)
        zt, it, ft, ot = jnp.split(gx, 4, axis=-1)
        zt = jnp.tanh(zt + rec[0])
        li = it + rec[1]
        lf = jax.nn.log_sigmoid(ft + rec[2])
        ot = jax.nn.sigmoid(ot + rec[3])
        m_new = jnp.maximum(lf + m, li)
        i_sc = jnp.exp(li - m_new)
        f_sc = jnp.exp(lf + m - m_new)
        c_new = f_sc * c + i_sc * zt
        n_new = jnp.maximum(f_sc * n + i_sc, jnp.exp(-m_new))
        hid_new = ot * (c_new / n_new)
        return (c_new, n_new, hid_new, m_new), hid_new

    carry0 = (state["c"], state["n"], state["h"], state["m"])
    carry, hs = jax.lax.scan(step, carry0,
                             jnp.moveaxis(gates_x.astype(jnp.float32), 1, 0))
    return jnp.moveaxis(hs, 0, 1), carry   # (B,S,d_in)


def init_slstm_state(cfg: ModelConfig, batch: int) -> dict:
    d_in, _, _, _, _ = _dims(cfg)
    x = cfg.xlstm or XLSTMConfig()
    return {
        "c": jnp.zeros((batch, d_in), jnp.float32),
        "n": jnp.ones((batch, d_in), jnp.float32),
        "h": jnp.zeros((batch, d_in), jnp.float32),
        "m": jnp.zeros((batch, d_in), jnp.float32),
        "conv": jnp.zeros((batch, x.conv_kernel - 1, d_in),
                          jnp.dtype(cfg.dtype)),
    }


def slstm_state_axes() -> dict:
    return {"c": ("batch", "inner"), "n": ("batch", "inner"),
            "h": ("batch", "inner"), "m": ("batch", "inner"),
            "conv": ("batch", None, "inner")}


def _slstm_core(params: Params, x: jax.Array, cfg: ModelConfig, state: dict):
    d_in, h, _, _, dv = _dims(cfg)
    uz = jnp.einsum("bsd,de->bse", x, params["up"])
    u, z = jnp.split(uz, 2, axis=-1)
    c, conv_state = _causal_conv(u, params["conv_w"], params["conv_b"],
                                 state["conv"])
    c = jax.nn.silu(c.astype(jnp.float32)).astype(x.dtype)
    gates_x = jnp.einsum("bsd,dg->bsg", c, params["w_gates"]) \
        .astype(jnp.float32) + params["b_gates"]
    hs, carry = _slstm_scan(params, gates_x, h, dv, state)
    new_state = {"c": carry[0], "n": carry[1], "h": carry[2], "m": carry[3],
                 "conv": conv_state}
    y = _headnorm(hs.reshape(*hs.shape[:2], h, dv).astype(x.dtype),
                  params["norm"])
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    out = jnp.einsum("bsd,de->bse", y, params["down"])
    return logical_shard(out, "batch", "seq", "embed"), new_state


def slstm(params: Params, x: jax.Array, cfg: ModelConfig,
          chunk: int = 0, return_state: bool = False):
    out, state = _slstm_core(params, x, cfg,
                             init_slstm_state(cfg, x.shape[0]))
    return (out, state) if return_state else out


def slstm_step(params: Params, state: dict, x: jax.Array,
               cfg: ModelConfig) -> tuple[jax.Array, dict]:
    return _slstm_core(params, x, cfg, state)
