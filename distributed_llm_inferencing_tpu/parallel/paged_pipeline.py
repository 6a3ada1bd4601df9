"""Pipeline-parallel serving programs over the paged KV cache.

This closes the one serving gap pipeline parallelism had (a round-3
review ask): models too big for one slice's tp×ep could only be served
through ``engine.generate`` — no continuous batching, no paged cache, no
prefix reuse on exactly the models that need serving throughput most
(BASELINE.md config 5; the reference's own shard-across-machines
ambition, reference shard_model.py:8-115, which it never executed).

Both programs here are drop-in replacements for their single-stage
counterparts in models/transformer.py, dispatched by the batcher when
its mesh has ``pp > 1``:

- ``paged_decode_chunk_pp``  ≙ transformer.paged_decode_chunk
- ``paged_prefill_tail_pp``  ≙ transformer.paged_prefill_tail

Design (round-robin GPipe over the ``pp`` mesh axis, inside one
``jax.shard_map`` program — tensor parallelism inside each stage stays
under GSPMD auto axes, exactly like parallel/pipeline.py):

- Stage p owns layers [p*L/pp, (p+1)*L/pp) — params AND the paged pool
  carry the layer axis sharded over pp (parallel/sharding.py
  paged_cache_specs), so every cache read/write is stage-local.
- The R serving slots split into M = pp microbatches of R/pp slots; the
  microbatch is the pipelining unit. At tick t, stage p works on
  microbatch (t-p) mod pp at decode-iteration (t-p) div pp. Activations
  AND the per-microbatch decode state (current token, context length,
  aliveness) ride stage->stage+1 via ``jax.lax.ppermute``; the hop from
  the last stage back to stage 0 is how iteration d's sampled token
  becomes iteration d+1's input. With M = pp every stage is busy every
  steady-state tick; the fill/drain bubble is (pp-1)/(K*pp + pp-1) of
  the chunk.
- Decode keeps the side-buffer trick of the dense chunk: fresh K/V
  accumulates per stage in [L/pp, R, K, Hkv, hd], each tick's attention
  reads pool(<cl0) ++ side(<=d), and ONE post-loop scatter commits the
  chunk (never-written steps of dead slots land in the dummy block).
- Sampling (ops/sampling.py sample_batch, per-slot PRNG streams) runs at
  the last stage; every stage executes the same SPMD code with masks, so
  the program stays collective-deadlock-free by construction.

Host-side scheduling (admission waves, growth, preemption — the batcher)
is unchanged: these are pure device programs with the same argument
contract, so the lockstep mirror broadcasts them exactly like their
single-stage versions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from distributed_llm_inferencing_tpu.models.config import ModelConfig


def _split_params(params):
    """(layer-stacked subtree, everything else) — the two shard_map input
    groups: layers ride P("pp") on the stacked axis, the rest replicate."""
    other = {k: v for k, v in params.items() if k != "layers"}
    return params["layers"], other


def _specs(params_layers, other):
    layer_spec = jax.tree.map(lambda _: P("pp"), params_layers)
    other_spec = jax.tree.map(lambda _: P(), other)
    return layer_spec, other_spec


def paged_decode_chunk_pp(params, cfg: ModelConfig, k: int, tokens, paged,
                          block_tables, context_lens, seeds, steps0, temps,
                          tks, tps, ds, budget, eos_ids, dummy_block: int,
                          *, mesh: Mesh):
    """K decode iterations for R slots with the layer stack pipelined
    over ``pp``. Same contract as transformer.paged_decode_chunk:
    returns (toks [K, R] int32, emits [K, R] bool, new paged).

    Requires R % pp == 0 (the batcher rounds its slot count up). An int8
    pool (cfg.kv_quant) works like the dense chunk's: the per-layer
    gather dequantizes at read, the bf16 side buffer quantizes in the
    single post-loop scatter.
    """
    from distributed_llm_inferencing_tpu.models import transformer as tf
    from distributed_llm_inferencing_tpu.ops.attention import attend
    from distributed_llm_inferencing_tpu.ops.kvcache import (
        dequant_kv, quant_kv)
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        PagedKVCache, gather_seq)
    from distributed_llm_inferencing_tpu.ops.sampling import sample_batch

    pp = mesh.shape["pp"]
    r = tokens.shape[0]
    if r % pp:
        raise ValueError(f"slots {r} must divide over pp={pp}")
    mbsz = r // pp
    L = cfg.num_layers
    bs = paged.block_size
    mb = block_tables.shape[1]
    dt = jnp.dtype(cfg.dtype)
    quantized = paged.quantized
    cl0 = context_lens
    n_ticks = k * pp + pp - 1

    p_layers, p_other = _split_params(params)
    layer_spec, other_spec = _specs(p_layers, p_other)
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    def body(p_layers, p_other, pool_k, pool_v, pool_ks, pool_vs, tokens,
             cl0_, bt, seeds, steps0, temps, tks, tps, ds, budget,
             eos_ids):
        pd = dict(p_other)
        pd["layers"] = p_layers
        stage = jax.lax.axis_index("pp")
        is_last = stage == pp - 1
        L_loc = pool_k.shape[0]
        assert L_loc == L // pp

        def mrows(a, m):
            return jax.lax.dynamic_slice_in_dim(a, m * mbsz, mbsz, 0)

        side0 = jnp.zeros((L_loc, r, k, cfg.num_kv_heads, cfg.head_dim), dt)
        x0 = jnp.zeros((mbsz, 1, cfg.hidden_size), dt)
        toks0 = jnp.zeros((k, r), jnp.int32)
        flags0 = jnp.zeros((k, r), jnp.int32)   # emits / wrote as int
        carry0 = (x0, jnp.zeros((mbsz,), jnp.int32),
                  jnp.zeros((mbsz,), jnp.int32), jnp.zeros((mbsz,), bool),
                  side0, side0, toks0, flags0, flags0)

        def tick(t, carry):
            (x, cur, cl, alive, side_k, side_v, toks_buf, emits_buf,
             wrote_buf) = carry
            j = t - stage
            valid = (j >= 0) & (j < k * pp)
            m = jnp.where(valid, j % pp, 0)
            d = jnp.where(valid, j // pp, 0)

            # stage 0 injects microbatch t at tick t (fill phase)
            fresh = (stage == 0) & (t < pp)
            cur = jnp.where(fresh, mrows(tokens, m), cur)
            cl = jnp.where(fresh, mrows(cl0_, m), cl)
            alive = jnp.where(fresh, mrows(budget, m) > 0, alive)

            q_pos = jnp.where(alive, cl, 0)[:, None]            # [mb, 1]
            x_emb = tf.embed(pd, cfg, cur[:, None], q_pos)
            x_in = jnp.where(stage == 0, x_emb, x)

            bt_m = mrows(bt, m)                                 # [mb, MB]
            cl0_m = mrows(cl0_, m)
            pool_pos = jnp.broadcast_to(
                jnp.arange(mb * bs, dtype=jnp.int32), (mbsz, mb * bs))
            pool_valid = pool_pos < cl0_m[:, None]
            side_pos = cl0_m[:, None] + jnp.arange(k, dtype=jnp.int32)[None]
            side_valid = jnp.broadcast_to(
                jnp.arange(k, dtype=jnp.int32)[None, :] <= d, (mbsz, k))

            def layer(xc, layer_in):
                if quantized:
                    lp, sk, sv, ck, cv, cks, cvs = layer_in
                    kp = dequant_kv(gather_seq(ck, bt_m),
                                    gather_seq(cks, bt_m), dt)
                    vp = dequant_kv(gather_seq(cv, bt_m),
                                    gather_seq(cvs, bt_m), dt)
                else:
                    lp, sk, sv, ck, cv = layer_in
                    kp = gather_seq(ck, bt_m)
                    vp = gather_seq(cv, bt_m)
                sk_m = jax.lax.dynamic_slice_in_dim(sk, m * mbsz, mbsz, 0)
                sv_m = jax.lax.dynamic_slice_in_dim(sv, m * mbsz, mbsz, 0)

                def attend_write(q, kh, vh):
                    sk2 = jax.lax.dynamic_update_slice(
                        sk_m, kh.astype(dt), (0, d, 0, 0))
                    sv2 = jax.lax.dynamic_update_slice(
                        sv_m, vh.astype(dt), (0, d, 0, 0))
                    attn = attend(
                        q, (kp, sk2), (vp, sv2), q_pos,
                        (pool_pos, side_pos), (pool_valid, side_valid),
                        sliding_window=tf._layer_window(cfg, lp),
                        alibi=tf._alibi(cfg), softcap=cfg.attn_softcap)
                    return attn, (sk2, sv2)

                xc, (sk2, sv2) = tf._block_body(xc, lp, cfg, q_pos,
                                                attend_write)
                sk = jax.lax.dynamic_update_slice_in_dim(
                    sk, jnp.where(valid, sk2, sk_m), m * mbsz, 0)
                sv = jax.lax.dynamic_update_slice_in_dim(
                    sv, jnp.where(valid, sv2, sv_m), m * mbsz, 0)
                return xc, (sk, sv)

            xs = (p_layers, side_k, side_v, pool_k, pool_v)
            if quantized:
                xs = xs + (pool_ks, pool_vs)
            x2, (side_k, side_v) = jax.lax.scan(layer, x_in, xs)

            # last stage: sample, record, advance the microbatch's state
            logits = tf.unembed(pd, cfg, x2)[:, 0]              # [mb, V]
            nxt = sample_batch(logits, mrows(seeds, m),
                               mrows(steps0, m) + d, mrows(temps, m),
                               mrows(tks, m), mrows(tps, m), mrows(ds, m))
            eos_m = mrows(eos_ids, m)
            is_eos = alive & (eos_m >= 0) & (nxt == eos_m)
            emit = alive & ~is_eos
            new_cl = cl + alive.astype(cl.dtype)
            new_alive = emit & (d + 1 < mrows(budget, m))
            do_upd = valid & is_last

            def record(buf, vals):
                old = jax.lax.dynamic_slice(buf, (d, m * mbsz), (1, mbsz))
                new = jnp.where(do_upd, vals.astype(buf.dtype), old[0])
                return jax.lax.dynamic_update_slice(buf, new[None],
                                                    (d, m * mbsz))

            toks_buf = record(toks_buf, nxt)
            emits_buf = record(emits_buf, emit)
            wrote_buf = record(wrote_buf, alive)   # alive at write time

            cur = jnp.where(do_upd, nxt, cur)
            cl = jnp.where(do_upd, new_cl, cl)
            alive = jnp.where(do_upd, new_alive, alive)

            # ring hop: activations + microbatch state to the next stage
            # (last -> 0 wraps the sampled token into the next iteration)
            x2 = jax.lax.ppermute(x2, "pp", perm)
            cur = jax.lax.ppermute(cur, "pp", perm)
            cl = jax.lax.ppermute(cl, "pp", perm)
            alive = jax.lax.ppermute(alive, "pp", perm)
            return (x2, cur, cl, alive, side_k, side_v, toks_buf,
                    emits_buf, wrote_buf)

        (_, _, _, _, side_k, side_v, toks_buf, emits_buf, wrote_buf) = \
            jax.lax.fori_loop(0, n_ticks, tick, carry0)

        # only the last stage recorded real values
        toks = jax.lax.psum(toks_buf, "pp")
        emits = jax.lax.psum(emits_buf, "pp") > 0
        wrote = jax.lax.psum(wrote_buf, "pp") > 0                # [k, R]

        # ONE scatter of the chunk's K/V into this stage's pool slice
        pos = cl0_[None, :] + jnp.arange(k, dtype=jnp.int32)[:, None]
        blk = jnp.take_along_axis(bt, jnp.swapaxes(pos // bs, 0, 1), axis=1)
        blk = jnp.where(wrote, jnp.swapaxes(blk, 0, 1), dummy_block)
        off = pos % bs
        if quantized:
            k8, ks = quant_kv(side_k)
            v8, vs = quant_kv(side_v)
            return (toks, emits,
                    pool_k.at[:, blk, off].set(jnp.swapaxes(k8, 1, 2)),
                    pool_v.at[:, blk, off].set(jnp.swapaxes(v8, 1, 2)),
                    pool_ks.at[:, blk, off].set(jnp.swapaxes(ks, 1, 2)),
                    pool_vs.at[:, blk, off].set(jnp.swapaxes(vs, 1, 2)))
        new_k = pool_k.at[:, blk, off].set(jnp.swapaxes(side_k, 1, 2))
        new_v = pool_v.at[:, blk, off].set(jnp.swapaxes(side_v, 1, 2))
        return toks, emits, new_k, new_v, pool_ks, pool_vs

    cache_spec = P("pp")
    # the scale planes ride as zero-size dummies when unquantized so one
    # body signature serves both layouts (shard_map specs stay static)
    dummy = jnp.zeros((L, 0), jnp.float32)
    pool_ks = paged.k_scale if quantized else dummy
    pool_vs = paged.v_scale if quantized else dummy
    toks, emits, new_k, new_v, new_ks, new_vs = jax.shard_map(
        body, mesh=mesh, axis_names={"pp"},
        in_specs=(layer_spec, other_spec, cache_spec, cache_spec,
                  cache_spec, cache_spec,
                  P(), P(), P(), P(), P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(), P(), cache_spec, cache_spec, cache_spec,
                   cache_spec),
        check_vma=False,
    )(p_layers, p_other, paged.k, paged.v, pool_ks, pool_vs, tokens,
      context_lens, block_tables, seeds, steps0, temps, tks, tps, ds,
      budget, eos_ids)
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import PagedKVCache
    if quantized:
        return toks, emits, PagedKVCache(k=new_k, v=new_v, k_scale=new_ks,
                                         v_scale=new_vs)
    return toks, emits, PagedKVCache(k=new_k, v=new_v)


def paged_speculative_chunk_pp(params, cfg: ModelConfig, k: int, gamma: int,
                               tokens, history, paged, block_tables,
                               context_lens, seeds, steps0, temps, tks, tps,
                               ds, budget, eos_ids, dummy_block: int,
                               gammas=None, *, mesh: Mesh):
    """K speculative iterations with the layer stack pipelined over
    ``pp``. Same contract as transformer.paged_speculative_chunk:
    returns (toks [K, R, gamma+1], keeps [K, R], eos_seen [K, R],
    new paged) — including the per-slot ``gammas`` draft widths
    (wave-level speculation; ``gamma`` stays the static maximum).

    This is the round-3/4 gap closed one level up: speculation pays most
    exactly where decode is slowest — the pp-sharded big models — and
    was previously refused at batcher construction. The GPipe schedule
    is paged_decode_chunk_pp's (microbatch (t-stage) mod pp at iteration
    (t-stage) div pp; activations AND per-microbatch decode state ride
    ``ppermute``); the speculative machinery is the single-stage
    chunk's, with two pipeline-specific twists:

    - The draft/acceptance STATE rides the ring alongside the
      activations: the token history (drafting source), the per-entry
      side positions and committed-entry mask (attention validity), and
      the emitted/eos bookkeeping. Stage 0 drafts (the history arrives
      with the microbatch), every stage attends pool + committed side
      entries + the current block, the last stage runs the exact
      leave-one-out rejection (ops/speculative.py accept_rejection_batch)
      and updates the riding state before it wraps to stage 0.
    - The post-loop pool scatter needs every microbatch's FINAL
      side_pos/acc_mask on every stage, but each final state ends the
      loop held by exactly one stage (states keep circulating unchanged
      once their k iterations are done, so after the last tick the pp
      in-flight states are the pp microbatches' finals). Each state
      carries its microbatch id; one psum of id-scattered buffers
      reassembles the full [R, E] masks everywhere, then each stage
      scatters its local side K/V slice exactly like the single-stage
      version.
    """
    from distributed_llm_inferencing_tpu.models import transformer as tf
    from distributed_llm_inferencing_tpu.ops.attention import attend
    from distributed_llm_inferencing_tpu.ops.kvcache import (
        dequant_kv, quant_kv)
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        PagedKVCache, gather_seq)
    from distributed_llm_inferencing_tpu.ops.speculative import (
        accept_rejection_batch, propose_ngram_device)

    pp = mesh.shape["pp"]
    r = tokens.shape[0]
    if r % pp:
        raise ValueError(f"slots {r} must divide over pp={pp}")
    mbsz = r // pp
    L = cfg.num_layers
    bs = paged.block_size
    mb = block_tables.shape[1]
    g1 = gamma + 1
    E = k * g1
    dt = jnp.dtype(cfg.dtype)
    quantized = paged.quantized
    cl0 = context_lens
    H = history.shape[1]
    n_ticks = k * pp + pp - 1
    entry_step = jnp.arange(E, dtype=jnp.int32) // g1              # [E]

    p_layers, p_other = _split_params(params)
    layer_spec, other_spec = _specs(p_layers, p_other)
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    def body(p_layers, p_other, pool_k, pool_v, pool_ks, pool_vs, tokens,
             history, cl0_, bt, seeds, steps0, temps, tks, tps, ds, budget,
             eos_ids):
        pd = dict(p_other)
        pd["layers"] = p_layers
        stage = jax.lax.axis_index("pp")
        is_last = stage == pp - 1
        L_loc = pool_k.shape[0]
        assert L_loc == L // pp

        def mrows(a, m):
            return jax.lax.dynamic_slice_in_dim(a, m * mbsz, mbsz, 0)

        side0 = jnp.zeros((L_loc, r, E, cfg.num_kv_heads, cfg.head_dim), dt)
        # ring state: one microbatch's speculation context
        state0 = dict(
            x=jnp.zeros((mbsz, g1, cfg.hidden_size), dt),
            cur=jnp.zeros((mbsz,), jnp.int32),
            drafts=jnp.zeros((mbsz, gamma), jnp.int32),
            hist=jnp.zeros((mbsz, H), jnp.int32),
            hist_len=jnp.zeros((mbsz,), jnp.int32),
            cl=jnp.zeros((mbsz,), jnp.int32),
            alive=jnp.zeros((mbsz,), bool),
            emitted=jnp.zeros((mbsz,), jnp.int32),
            eos_seen=jnp.zeros((mbsz,), bool),
            side_pos=jnp.zeros((mbsz, E), jnp.int32),
            acc=jnp.zeros((mbsz, E), bool),
            m_id=jnp.asarray(-1, jnp.int32),
        )
        toks0 = jnp.zeros((k, r, g1), jnp.int32)
        flags0 = jnp.zeros((k, r), jnp.int32)
        carry0 = (state0, side0, side0, toks0, flags0, flags0)

        def tick(t, carry):
            st, side_k, side_v, toks_buf, keeps_buf, eos_buf = carry
            j = t - stage
            valid = (j >= 0) & (j < k * pp)
            m = jnp.where(valid, j % pp, 0)
            d = jnp.where(valid, j // pp, 0)

            # stage 0 injects microbatch t at tick t (fill phase)
            fresh = (stage == 0) & (t < pp)

            def inj(old, new):
                return jnp.where(fresh, new, old)

            cur = inj(st["cur"], mrows(tokens, m))
            hist = jnp.where(fresh, mrows(history, m), st["hist"])
            hist_len = inj(st["hist_len"], mrows(cl0_, m) + 1)
            cl = inj(st["cl"], mrows(cl0_, m))
            alive = jnp.where(fresh, mrows(budget, m) > 0, st["alive"])
            emitted = inj(st["emitted"], jnp.zeros((mbsz,), jnp.int32))
            eos_seen = jnp.where(fresh, jnp.zeros((mbsz,), bool),
                                 st["eos_seen"])
            side_pos_m = jnp.where(fresh, jnp.zeros((mbsz, E), jnp.int32),
                                   st["side_pos"])
            acc_m = jnp.where(fresh, jnp.zeros((mbsz, E), bool), st["acc"])
            m_id = jnp.where(fresh, t, st["m_id"])

            qp0 = jnp.where(alive, cl, 0)
            qp = qp0[:, None] + jnp.arange(g1, dtype=jnp.int32)[None, :]

            # stage 0 drafts from the riding history; later stages keep
            # the drafts that rode in with the activations
            drafts_new, _ = propose_ngram_device(hist, hist_len, gamma)
            drafts = jnp.where(stage == 0, drafts_new, st["drafts"])
            toks_in = jnp.concatenate([cur[:, None], drafts], axis=1)
            x_emb = tf.embed(pd, cfg, toks_in, qp)
            x_in = jnp.where(stage == 0, x_emb, st["x"])

            upd = jax.lax.dynamic_update_slice(side_pos_m, qp, (0, d * g1))
            side_pos_m = jnp.where(valid, upd, side_pos_m)
            is_cur_block = jnp.broadcast_to(entry_step == d, (mbsz, E))
            side_valid = acc_m | is_cur_block

            bt_m = mrows(bt, m)
            cl0_m = mrows(cl0_, m)
            pool_pos = jnp.broadcast_to(
                jnp.arange(mb * bs, dtype=jnp.int32), (mbsz, mb * bs))
            pool_valid = pool_pos < cl0_m[:, None]

            def layer(xc, layer_in):
                if quantized:
                    lp, sk, sv, ck, cv, cks, cvs = layer_in
                    kp = dequant_kv(gather_seq(ck, bt_m),
                                    gather_seq(cks, bt_m), dt)
                    vp = dequant_kv(gather_seq(cv, bt_m),
                                    gather_seq(cvs, bt_m), dt)
                else:
                    lp, sk, sv, ck, cv = layer_in
                    kp = gather_seq(ck, bt_m)
                    vp = gather_seq(cv, bt_m)
                sk_m = jax.lax.dynamic_slice_in_dim(sk, m * mbsz, mbsz, 0)
                sv_m = jax.lax.dynamic_slice_in_dim(sv, m * mbsz, mbsz, 0)

                def attend_write(q, kh, vh):
                    sk2 = jax.lax.dynamic_update_slice(
                        sk_m, kh.astype(dt), (0, d * g1, 0, 0))
                    sv2 = jax.lax.dynamic_update_slice(
                        sv_m, vh.astype(dt), (0, d * g1, 0, 0))
                    attn = attend(
                        q, (kp, sk2), (vp, sv2), qp,
                        (pool_pos, side_pos_m), (pool_valid, side_valid),
                        sliding_window=tf._layer_window(cfg, lp),
                        alibi=tf._alibi(cfg), softcap=cfg.attn_softcap)
                    return attn, (sk2, sv2)

                xc, (sk2, sv2) = tf._block_body(xc, lp, cfg, qp,
                                                attend_write)
                sk = jax.lax.dynamic_update_slice_in_dim(
                    sk, jnp.where(valid, sk2, sk_m), m * mbsz, 0)
                sv = jax.lax.dynamic_update_slice_in_dim(
                    sv, jnp.where(valid, sv2, sv_m), m * mbsz, 0)
                return xc, (sk, sv)

            xs = (p_layers, side_k, side_v, pool_k, pool_v)
            if quantized:
                xs = xs + (pool_ks, pool_vs)
            x2, (side_k, side_v) = jax.lax.scan(layer, x_in, xs)

            # last stage: exact acceptance + state advance (the same
            # bookkeeping as the single-stage chunk, per-microbatch)
            logits = tf.unembed(pd, cfg, x2)                  # [mb, g1, V]
            toks_out, n_emit = accept_rejection_batch(
                logits, drafts, mrows(seeds, m), mrows(steps0, m) + emitted,
                mrows(temps, m), mrows(tks, m), mrows(tps, m), mrows(ds, m),
                widths=(mrows(gammas, m) if gammas is not None else None))
            idx = jnp.arange(g1, dtype=jnp.int32)[None, :]
            eos_m = mrows(eos_ids, m)
            emit_sl = idx < n_emit[:, None]
            is_eos = (toks_out == eos_m[:, None]) & (eos_m >= 0)[:, None] \
                & emit_sl
            eos_pos = jnp.min(jnp.where(is_eos, idx, g1), axis=1)
            rem = mrows(budget, m) - emitted
            n_keep = jnp.minimum(jnp.minimum(n_emit, eos_pos), rem)
            n_keep = jnp.where(alive, n_keep, 0)
            hit_eos = (eos_pos < n_emit) & (eos_pos < rem)

            commit = (idx < n_keep[:, None]) | ((idx == 0) & alive[:, None])
            acc_upd = jax.lax.dynamic_update_slice(acc_m, commit,
                                                   (0, d * g1))
            rows = jnp.broadcast_to(jnp.arange(mbsz)[:, None], (mbsz, g1))
            cols = jnp.where(emit_sl & (idx < n_keep[:, None]),
                             cl[:, None] + 1 + idx, H)
            hist_upd = hist.at[rows, cols].set(toks_out, mode="drop")
            new_cur = jnp.where(
                n_keep > 0,
                jnp.take_along_axis(
                    toks_out, jnp.maximum(n_keep - 1, 0)[:, None],
                    axis=1)[:, 0],
                cur)

            do_upd = valid & is_last
            acc_m = jnp.where(do_upd, acc_upd, acc_m)
            hist = jnp.where(do_upd, hist_upd, hist)
            hist_len = jnp.where(do_upd, hist_len + n_keep, hist_len)
            cl = jnp.where(do_upd, cl + n_keep, cl)
            emitted = jnp.where(do_upd, emitted + n_keep, emitted)
            eos_seen = jnp.where(do_upd, eos_seen | (hit_eos & alive),
                                 eos_seen)
            alive = jnp.where(do_upd,
                              alive & ~hit_eos
                              & (emitted < mrows(budget, m)), alive)
            cur = jnp.where(do_upd, new_cur, cur)

            def record(buf, vals):
                start = (d,) + (m * mbsz,) + (0,) * (buf.ndim - 2)
                sizes = (1, mbsz) + buf.shape[2:]
                old = jax.lax.dynamic_slice(buf, start, sizes)
                new = jnp.where(do_upd, vals.astype(buf.dtype), old[0])
                return jax.lax.dynamic_update_slice(buf, new[None], start)

            toks_buf = record(toks_buf, toks_out)
            keeps_buf = record(keeps_buf, n_keep)
            eos_buf = record(eos_buf, eos_seen)

            st2 = dict(
                x=jax.lax.ppermute(x2, "pp", perm),
                cur=jax.lax.ppermute(cur, "pp", perm),
                drafts=jax.lax.ppermute(drafts, "pp", perm),
                hist=jax.lax.ppermute(hist, "pp", perm),
                hist_len=jax.lax.ppermute(hist_len, "pp", perm),
                cl=jax.lax.ppermute(cl, "pp", perm),
                alive=jax.lax.ppermute(alive, "pp", perm),
                emitted=jax.lax.ppermute(emitted, "pp", perm),
                eos_seen=jax.lax.ppermute(eos_seen, "pp", perm),
                side_pos=jax.lax.ppermute(side_pos_m, "pp", perm),
                acc=jax.lax.ppermute(acc_m, "pp", perm),
                m_id=jax.lax.ppermute(m_id, "pp", perm),
            )
            return (st2, side_k, side_v, toks_buf, keeps_buf, eos_buf)

        st, side_k, side_v, toks_buf, keeps_buf, eos_buf = jax.lax.fori_loop(
            0, n_ticks, tick, carry0)

        # reassemble the final [R, E] commit masks from the circulating
        # states (each stage ends holding exactly one microbatch's final)
        row0 = st["m_id"] * mbsz
        acc_all = jax.lax.psum(
            jax.lax.dynamic_update_slice(
                jnp.zeros((r, E), jnp.int32), st["acc"].astype(jnp.int32),
                (row0, 0)), "pp") > 0
        pos_all = jax.lax.psum(
            jax.lax.dynamic_update_slice(
                jnp.zeros((r, E), jnp.int32), st["side_pos"], (row0, 0)),
            "pp")

        toks = jax.lax.psum(toks_buf, "pp")
        keeps = jax.lax.psum(keeps_buf, "pp")
        eos_seen = jax.lax.psum(eos_buf, "pp") > 0

        blk = jnp.take_along_axis(bt, pos_all // bs, axis=1)       # [R, E]
        blk = jnp.where(acc_all, blk, dummy_block)
        off = pos_all % bs
        if quantized:
            k8, ks = quant_kv(side_k)
            v8, vs = quant_kv(side_v)
            return (toks, keeps, eos_seen,
                    pool_k.at[:, blk, off].set(k8),
                    pool_v.at[:, blk, off].set(v8),
                    pool_ks.at[:, blk, off].set(ks),
                    pool_vs.at[:, blk, off].set(vs))
        return (toks, keeps, eos_seen,
                pool_k.at[:, blk, off].set(side_k),
                pool_v.at[:, blk, off].set(side_v), pool_ks, pool_vs)

    cache_spec = P("pp")
    dummy = jnp.zeros((L, 0), jnp.float32)
    pool_ks = paged.k_scale if quantized else dummy
    pool_vs = paged.v_scale if quantized else dummy
    toks, keeps, eos_seen, new_k, new_v, new_ks, new_vs = jax.shard_map(
        body, mesh=mesh, axis_names={"pp"},
        in_specs=(layer_spec, other_spec, cache_spec, cache_spec,
                  cache_spec, cache_spec,
                  P(), P(), P(), P(), P(), P(), P(), P(), P(), P(), P(),
                  P()),
        out_specs=(P(), P(), P(), cache_spec, cache_spec, cache_spec,
                   cache_spec),
        check_vma=False,
    )(p_layers, p_other, paged.k, paged.v, pool_ks, pool_vs, tokens,
      history, context_lens, block_tables, seeds, steps0, temps, tks, tps,
      ds, budget, eos_ids)
    if quantized:
        return toks, keeps, eos_seen, PagedKVCache(
            k=new_k, v=new_v, k_scale=new_ks, v_scale=new_vs)
    return toks, keeps, eos_seen, PagedKVCache(k=new_k, v=new_v)


def paged_prefill_tail_pp(params, cfg: ModelConfig, tokens, tail_len,
                          tail_blocks, prefix_blocks, prefix_len, paged,
                          dummy_block: int, *, mesh: Mesh):
    """Admission-wave tail prefill with the layer stack pipelined over
    ``pp``. Same contract as transformer.paged_prefill_tail: returns
    (last-token logits [B, V] f32, new paged). Wave rows microbatch over
    pp (B % pp == 0 — the batcher pads its wave buckets); each microbatch
    makes one pass through the stages (2*pp - 1 ticks). ``dummy_block``
    absorbs the fill/drain ticks' garbage writes (the dense version gets
    this for free from the host's all-dummy padding rows). int8 pools
    store quantized tail K/V + scales exactly like the dense version.
    """
    from distributed_llm_inferencing_tpu.models import transformer as tf
    from distributed_llm_inferencing_tpu.ops.kvcache import quant_kv
    from distributed_llm_inferencing_tpu.ops.paged_kvcache import (
        PagedKVCache, paged_attend_prefix, write_block_run)

    pp = mesh.shape["pp"]
    b, t = tokens.shape
    if b % pp:
        raise ValueError(f"wave of {b} rows must divide over pp={pp}")
    if tail_blocks.ndim == 1:
        tail_blocks = tail_blocks[None]
    mbsz = b // pp
    dt = jnp.dtype(cfg.dtype)
    quantized = paged.quantized
    n_ticks = 2 * pp - 1

    p_layers, p_other = _split_params(params)
    layer_spec, other_spec = _specs(p_layers, p_other)
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    q_pos_all = prefix_len[:, None] + jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.int32), (b, t))
    tail_valid_all = (jnp.arange(t, dtype=jnp.int32)[None, :]
                      < tail_len[:, None])

    def body(p_layers, p_other, pool_k, pool_v, pool_ks, pool_vs, tokens,
             tail_len, tail_bs, prefix_bs, prefix_len, q_pos_all,
             tail_valid_all):
        pd = dict(p_other)
        pd["layers"] = p_layers
        stage = jax.lax.axis_index("pp")
        is_last = stage == pp - 1

        def mrows(a, m):
            return jax.lax.dynamic_slice_in_dim(a, m * mbsz, mbsz, 0)

        x0 = jnp.zeros((mbsz, t, cfg.hidden_size), dt)
        out0 = jnp.zeros((b, cfg.vocab_size), jnp.float32)
        carry0 = (x0, pool_k, pool_v, pool_ks, pool_vs, out0)

        def tick(tt, carry):
            x, pool_k, pool_v, pool_ks, pool_vs, out = carry
            j = tt - stage
            valid = (j >= 0) & (j < pp)
            m = jnp.where(valid, j, 0)

            qp = mrows(q_pos_all, m)
            tv = mrows(tail_valid_all, m)
            tb_m = mrows(tail_bs, m)
            pb_m = mrows(prefix_bs, m)
            pl_m = mrows(prefix_len, m)

            x_emb = tf.embed(pd, cfg, mrows(tokens, m), qp)
            x_in = jnp.where(stage == 0, x_emb, x)

            def layer(xc, layer_in):
                def attend_write_quant(q, kh, vh):
                    lp, ck, cv, cks, cvs = layer_in
                    tb_eff = jnp.where(valid, tb_m, dummy_block)
                    k8, ks = quant_kv(kh)
                    v8, vs = quant_kv(vh)
                    nk = write_block_run(ck, k8, tb_eff)
                    nv = write_block_run(cv, v8, tb_eff)
                    nks = write_block_run(cks, ks, tb_eff)
                    nvs = write_block_run(cvs, vs, tb_eff)
                    # the tail attends its own fresh bf16 K/V plus the
                    # dequantized cached prefix
                    attn = paged_attend_prefix(
                        q, kh, vh, nk, nv, pb_m, pl_m, qp, tv,
                        sliding_window=tf._layer_window(cfg, lp),
                        k_scale_layer=nks, v_scale_layer=nvs,
                        alibi=tf._alibi(cfg), softcap=cfg.attn_softcap)
                    return attn, (nk, nv, nks, nvs)

                def attend_write(q, kh, vh):
                    # write this microbatch's tail K/V; invalid ticks
                    # write only the dummy block (padding-row semantics)
                    lp, ck, cv = layer_in
                    tb_eff = jnp.where(valid, tb_m, dummy_block)
                    nk = write_block_run(ck, kh, tb_eff)
                    nv = write_block_run(cv, vh, tb_eff)
                    attn = paged_attend_prefix(
                        q, kh, vh, nk, nv, pb_m, pl_m, qp, tv,
                        sliding_window=tf._layer_window(cfg, lp),
                        alibi=tf._alibi(cfg), softcap=cfg.attn_softcap)
                    return attn, (nk, nv)

                lp = layer_in[0]
                xc, caches = tf._block_body(
                    xc, lp, cfg, qp,
                    attend_write_quant if quantized else attend_write)
                return xc, caches

            if quantized:
                x2, (pool_k, pool_v, pool_ks, pool_vs) = jax.lax.scan(
                    layer, x_in,
                    (p_layers, pool_k, pool_v, pool_ks, pool_vs))
            else:
                x2, (pool_k, pool_v) = jax.lax.scan(
                    layer, x_in, (p_layers, pool_k, pool_v))

            # last stage: project the last real position of each row
            tl_m = mrows(tail_len, m)
            last_x = jnp.take_along_axis(
                x2, jnp.maximum(tl_m - 1, 0)[:, None, None].astype(
                    jnp.int32), axis=1)
            logits = tf.unembed(pd, cfg, last_x)[:, 0]          # [mb, V]
            old = jax.lax.dynamic_slice(out, (m * mbsz, 0), (mbsz,
                                                             out.shape[1]))
            new = jnp.where(valid & is_last, logits, old)
            out = jax.lax.dynamic_update_slice(out, new, (m * mbsz, 0))

            x2 = jax.lax.ppermute(x2, "pp", perm)
            return (x2, pool_k, pool_v, pool_ks, pool_vs, out)

        _, pool_k, pool_v, pool_ks, pool_vs, out = jax.lax.fori_loop(
            0, n_ticks, tick, carry0)
        return jax.lax.psum(out, "pp"), pool_k, pool_v, pool_ks, pool_vs

    cache_spec = P("pp")
    dummy = jnp.zeros((cfg.num_layers, 0), jnp.float32)
    pool_ks = paged.k_scale if quantized else dummy
    pool_vs = paged.v_scale if quantized else dummy
    last, new_k, new_v, new_ks, new_vs = jax.shard_map(
        body, mesh=mesh, axis_names={"pp"},
        in_specs=(layer_spec, other_spec, cache_spec, cache_spec,
                  cache_spec, cache_spec,
                  P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(), cache_spec, cache_spec, cache_spec, cache_spec),
        check_vma=False,
    )(p_layers, p_other, paged.k, paged.v, pool_ks, pool_vs, tokens,
      tail_len, tail_blocks, prefix_blocks, prefix_len, q_pos_all,
      tail_valid_all)
    if quantized:
        return last, PagedKVCache(k=new_k, v=new_v, k_scale=new_ks,
                                  v_scale=new_vs)
    return last, PagedKVCache(k=new_k, v=new_v)
