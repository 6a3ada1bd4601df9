"""Mamba-2 state-space mixer (Falcon-H1's, cfg.ssm), in plain jax.numpy.

One mixer a block, beside the attention heads (models/transformer.py
_block_body). Its per-request memory is not K and V rows but a recurrent
state ``S`` [H, P, N] (float32: a recurrence of thousands of steps in
bfloat16 drifts) and the last d_conv - 1 inputs of a causal depthwise
convolution (``conv``, [d_conv - 1, conv_dim] stored flat, the model's
dtype), both overwritten on every token. With z, x, B, C, dt the five
parts of in_proj's output (x, B and C after the convolution and silu),
A = -exp(A_log), dt = softplus(dt + dt_bias), and g(p) the group of
head p:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t^g
    y_t = S_t C_t^g + D x_t

Two forms compute it: ``mix_tokens`` a block of positions a row (a
prompt's tail, forward()) as the chunked scan -- inside a chunk of
cfg.ssm.chunk_size positions one masked [Q, Q] product (the SSD form),
the state passed between chunks by a lax.scan -- and ``mix_step`` one
position a row (a decode pass). Positions marked invalid (a padded tail
bucket's, a padded wave row's, a dead slot's) advance neither the state
nor the window: their dt is zero, which is decay one and input zero, and
the window is taken from the last valid inputs.

The named scopes (ssm_in_proj, ssm_conv, ssm_scan, ssm_step,
ssm_gate_norm, ssm_out_proj) are what scripts/profile_summary.py and
the benchmark's trace readers find the mixer's device time by.
models/reference/falcon_h1_ref.py is the same mathematics token by
token in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_llm_inferencing_tpu.models.config import ModelConfig

F32 = jnp.float32


def _project(h, lp, cfg: ModelConfig, linear):
    """in_proj and its split: z [.., d_ssm], xBC [.., conv_dim] (before
    the convolution), dt [.., H] float32 after softplus."""
    c = cfg.ssm
    with jax.named_scope("ssm_in_proj"):
        mup = jnp.concatenate([
            jnp.full((n,), m, F32) for n, m in zip(
                (c.d_ssm, c.d_ssm, c.n_groups * c.d_state,
                 c.n_groups * c.d_state, c.n_heads), c.multipliers)])
        zxbcdt = linear(h * jnp.asarray(c.in_multiplier, h.dtype),
                        lp["in_proj"])
        zxbcdt = zxbcdt * mup.astype(zxbcdt.dtype)
        z = zxbcdt[..., :c.d_ssm]
        xbc = zxbcdt[..., c.d_ssm:c.d_ssm + c.conv_dim]
        dt = jax.nn.softplus(zxbcdt[..., c.d_ssm + c.conv_dim:].astype(F32)
                             + lp["dt_bias"].astype(F32))
    return z, xbc, dt


def _split_xbc(xbc, cfg: ModelConfig):
    """Convolved [.., conv_dim] -> x [.., H, P], B and C [.., G, N]."""
    c = cfg.ssm
    gn = c.n_groups * c.d_state
    lead = xbc.shape[:-1]
    x = xbc[..., :c.d_ssm].reshape(*lead, c.n_heads, c.d_head)
    b = xbc[..., c.d_ssm:c.d_ssm + gn].reshape(*lead, c.n_groups, c.d_state)
    cc = xbc[..., c.d_ssm + gn:].reshape(*lead, c.n_groups, c.d_state)
    return x, b, cc


def _conv_taps(lp, cfg: ModelConfig):
    """The depthwise filter [d_conv, conv_dim] and its bias, float32."""
    w = lp["conv"]["w"].astype(F32)
    b = lp["conv"]["b"].astype(F32) if cfg.ssm.conv_bias else 0.0
    return w, b


def _gate_out(y, z, lp, cfg: ModelConfig, linear):
    """y * silu(z), RMS over each group of d_ssm / n_groups, the scale,
    out_proj and ssm_out_multiplier. y [.., H, P] float32."""
    c = cfg.ssm
    lead = y.shape[:-2]
    with jax.named_scope("ssm_gate_norm"):
        y = y.reshape(*lead, c.d_ssm) * jax.nn.silu(z.astype(F32))
        yg = y.reshape(*lead, c.n_groups, c.d_ssm // c.n_groups)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                                + cfg.norm_eps)
        y = (yg.reshape(*lead, c.d_ssm)
             * lp["ssm_norm"]["scale"].astype(F32)).astype(z.dtype)
    with jax.named_scope("ssm_out_proj"):
        out = linear(y, lp["out_proj"])
        return out * jnp.asarray(c.out_multiplier, out.dtype)


def scan_chunks(x, dt, a_neg, b, cc, state, chunk: int):
    """The recurrence over T positions a row as a scan over chunks.

    x [B, T, H, P] (any float dtype), dt [B, T, H] float32 (0 where the
    position is not valid), a_neg [H] float32 (= -exp(A_log)), b and cc
    [B, T, G, N], state [B, H, P, N] float32. Returns (y [B, T, H, P]
    float32 without the D x term, the state after the last position).
    T is padded to whole chunks with dt = 0 positions."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    k = h // g
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, dt, b, cc = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                        for v in (x, dt, b, cc))
    nc = (t + pad) // q

    def chunks(v):   # [B, nc * Q, ...] -> [nc, B, Q, ...]
        return jnp.moveaxis(v.reshape(bsz, nc, q, *v.shape[2:]), 1, 0)

    tri = jnp.tril(jnp.ones((q, q), bool))

    def body(s, inp):
        xc, dtc, bc, ccc = inp
        acs = jnp.cumsum(dtc * a_neg, axis=1)                # [B, Q, H]
        # decay from position j to position i >= j inside the chunk
        seg = acs[:, :, None, :] - acs[:, None, :, :]        # [B, Qi, Qj, H]
        decay = jnp.exp(jnp.where(tri[None, :, :, None], seg, -jnp.inf))
        cb = jnp.einsum("bign,bjgn->bgij", ccc, bc,
                        preferred_element_type=F32)          # [B, G, Q, Q]
        wts = (jnp.moveaxis(decay, 3, 1).reshape(bsz, g, k, q, q)
               * cb[:, :, None])                             # [B, G, K, Qi, Qj]
        xdt = (xc.astype(F32) * dtc[..., None]).reshape(bsz, q, g, k, p)
        y = jnp.einsum("bgkij,bjgkp->bigkp", wts, xdt,
                       preferred_element_type=F32)
        # what the state before the chunk adds at position i
        sg = s.reshape(bsz, g, k, p, n)
        y = y + jnp.einsum("bign,bgkpn->bigkp", ccc.astype(F32), sg,
                           preferred_element_type=F32) \
            * jnp.exp(acs).reshape(bsz, q, g, k)[..., None]
        # the state after the chunk
        to_end = jnp.exp(acs[:, -1:, :] - acs).reshape(bsz, q, g, k)
        s_new = (jnp.exp(acs[:, -1]).reshape(bsz, g, k)[..., None, None] * sg
                 + jnp.einsum("bjgkp,bjgn->bgkpn", xdt * to_end[..., None],
                              bc.astype(F32), preferred_element_type=F32))
        return s_new.reshape(bsz, h, p, n), y.reshape(bsz, q, h, p)

    state, y = jax.lax.scan(body, state.astype(F32),
                            tuple(chunks(v) for v in (x, dt, b, cc)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, nc * q, h, p)
    return y[:, :t], state


def mix_tokens(h, lp, cfg: ModelConfig, state, conv, valid, linear):
    """The mixer over a block of positions a row.

    h [B, T, D] the block's normed input; state [B, H, P, N] float32 and
    conv [B, (d_conv - 1) * conv_dim] as they stood before the first
    position (zeros for a request's first); valid [B, T] bool, true on a
    prefix of each row. Returns (out [B, T, D], new state, new conv):
    the state and the window after each row's last valid position."""
    c = cfg.ssm
    bsz, t, _ = h.shape
    z, xbc, dt = _project(h, lp, cfg, linear)
    dt = jnp.where(valid[..., None], dt, 0.0)
    with jax.named_scope("ssm_conv"):
        w, bias = _conv_taps(lp, cfg)
        km1 = c.d_conv - 1
        cat = jnp.concatenate(
            [conv.reshape(bsz, km1, c.conv_dim).astype(xbc.dtype), xbc],
            axis=1)                                      # [B, K-1 + T, C]
        acc = sum(cat[:, j:j + t].astype(F32) * w[j] for j in range(c.d_conv))
        xbc_c = jax.nn.silu(acc + bias).astype(h.dtype)
        # the window after the last valid position: inputs n-3 .. n-1
        n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
        idx = n_valid[:, None] + jnp.arange(km1, dtype=jnp.int32)[None, :]
        new_conv = jnp.take_along_axis(cat, idx[..., None], axis=1) \
            .reshape(bsz, km1 * c.conv_dim).astype(conv.dtype)
    x, b, cc = _split_xbc(xbc_c, cfg)
    with jax.named_scope("ssm_scan"):
        y, new_state = scan_chunks(
            x, dt, -jnp.exp(lp["A_log"].astype(F32)), b, cc, state,
            c.chunk_size)
        y = y + lp["D"].astype(F32)[:, None] * x.astype(F32)
    return _gate_out(y, z, lp, cfg, linear), new_state, new_conv


def mix_step(h, lp, cfg: ModelConfig, plane, layer, conv, alive, linear,
             kernel=None):
    """The mixer for one position a row (a decode pass), over the state
    plane where it lies.

    h [R, 1, D]; plane [L, R + 1, H, P, N] float32, whose rows 0..R-1 at
    ``layer`` (a traced scalar under a layer scan) are the slots'
    states; conv [R, (d_conv - 1) * conv_dim]; alive [R] bool. Returns
    (out [R, 1, D], the plane with the layer's live rows advanced, new
    conv); a row that is not alive keeps the state and window it had.

    ``kernel`` ("pallas" | "pallas_interpret", the batcher's pin for a
    one-device TPU program, where ops/pallas/ssm_step.py takes the
    plane's shape): the update reads and writes a slot's state once, in
    place. Else the jax.numpy form, which XLA splits in two fusions that
    both read the state (PERF.md section 6, PR 41)."""
    c = cfg.ssm
    r = h.shape[0]
    z, xbc, dt = _project(h[:, 0], lp, cfg, linear)
    with jax.named_scope("ssm_conv"):
        w, bias = _conv_taps(lp, cfg)
        km1 = c.d_conv - 1
        win = conv.reshape(r, km1, c.conv_dim)
        acc = sum(win[:, j].astype(F32) * w[j] for j in range(km1)) \
            + xbc.astype(F32) * w[km1]
        xbc_c = jax.nn.silu(acc + bias).astype(h.dtype)
        new_conv = jnp.concatenate([win[:, 1:], xbc[:, None].astype(
            conv.dtype)], axis=1).reshape(r, km1 * c.conv_dim)
        new_conv = jnp.where(alive[:, None], new_conv, conv)
    x, b, cc = _split_xbc(xbc_c, cfg)                    # [R,H,P], [R,G,N]
    with jax.named_scope("ssm_step"):
        g, k = c.n_groups, c.n_heads // c.n_groups
        xf = x.astype(F32)
        # a dead row: decay one and input zero leave its state as it was
        decay = jnp.where(alive[:, None], jnp.exp(
            dt * -jnp.exp(lp["A_log"].astype(F32))), 1.0)         # [R, H]
        dtx = jnp.where(alive[:, None, None], xf * dt[..., None], 0.0)
        if kernel:
            from distributed_llm_inferencing_tpu.ops.pallas import ssm_step
            plane, y = ssm_step.ssm_step(
                plane, layer, decay, dtx, b.astype(F32), cc.astype(F32),
                interpret=kernel == "pallas_interpret")
        else:
            state = jax.lax.dynamic_slice(
                plane, (layer, 0, 0, 0, 0), (1, r) + plane.shape[2:])[0]
            sg = state.reshape(r, g, k, c.d_head, c.d_state)
            s_new = (decay.reshape(r, g, k)[..., None, None] * sg
                     + dtx.reshape(r, g, k, c.d_head)[..., None]
                     * b.astype(F32)[:, :, None, None, :])
            y = jnp.sum(s_new * cc.astype(F32)[:, :, None, None, :],
                        axis=-1).reshape(r, c.n_heads, c.d_head)
            plane = jax.lax.dynamic_update_slice(
                plane, s_new.reshape((1,) + state.shape),
                (layer, 0, 0, 0, 0))
        y = y + lp["D"].astype(F32)[:, None] * xf
    return _gate_out(y, z, lp, cfg, linear)[:, None], plane, new_conv
