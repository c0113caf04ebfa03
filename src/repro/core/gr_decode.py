"""End-to-end GR generation: one prefill + ND × (beam search + decode).

This is the engine-facing integration of the paper's three components for
dense-GQA GR models (OneRec-style):

  prefill         — prompt forward, KV installed once into the shared cache
  beam phase d    — xBeam expansion with valid-path constraints: dense
                    (R, BW, V) masks, or — with ``beam_select="sparse"`` —
                    a gather over the trie's padded-CSR child tables with
                    Top-K over the (R, BW, max_fanout) pool (paper §6
                    early sorting termination; no dense mask materialized)
  decode phase d  — one token per beam; staged xAttention against the
                    separated cache; unshared cache forked by parent index

Two execution modes mirror the paper's xSchedule ablation:
  * ``graph``  — the whole ND-phase loop is one jitted XLA program using
    device-resident masks (paper's kernel-graph dispatch + §9.5 device
    filtering).  One dispatch per request batch.
  * ``eager``  — per-phase jitted calls with *host* mask generation between
    them (the overlap-structured path; in the simulator the host mask time
    can overlap the device forward).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Dict, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import GRConfig, ModelConfig
from repro.core import xbeam
from repro.core.item_trie import ItemTrie, MaskWorkspace
from repro.core.kv_arena import gather_pages, page_slots
from repro.core.kv_cache import (SeparatedCache, chunk_slots,
                                 init_separated_cache, write_prefill,
                                 write_prefill_chunk)
from repro.core.xattention import paged_beam_attention, staged_beam_attention
from repro.models.attention import gqa_qkv, mha
from repro.models.common import apply_norm, dense
from repro.models.mlp import apply_mlp
from repro.models.model import TransformerModel
from repro.models.rope import apply_rope, rope_angles


class GRDecoder:
    """GR serving decoder over a dense-GQA ``TransformerModel``."""

    def __init__(self, cfg: ModelConfig, gr: GRConfig,
                 trie: Optional[ItemTrie] = None,
                 attention_impl: str = "staged"):
        assert cfg.attention_kind == "gqa", "GR decoder requires GQA models"
        self.cfg = cfg
        self.gr = gr
        self.trie = trie
        assert attention_impl in ("staged", "paged", "kernel")
        self.attention_impl = attention_impl
        if gr.beam_select not in ("dense", "sparse"):
            raise ValueError(f"unknown beam_select {gr.beam_select!r}; "
                             f"have ['dense', 'sparse']")
        if gr.beam_select == "sparse":
            if trie is None:
                raise ValueError("beam_select='sparse' gathers trie "
                                 "children; it requires an ItemTrie")
            if trie.nd < gr.num_decode_phases:
                raise ValueError(
                    f"trie depth {trie.nd} does not cover "
                    f"{gr.num_decode_phases} decode phases")
        self._sparse = gr.beam_select == "sparse"
        self.model = TransformerModel(cfg)
        self._backends: Dict[str, "ExecutionBackend"] = {}

    def candidate_pool_sizes(self) -> list:
        """Per-phase candidate-pool width each beam's select scans: the trie
        level's max fanout on the sparse path, the full vocab on the dense
        one (feeds the engine's ``beam_pool`` early-termination stats)."""
        nd = self.gr.num_decode_phases
        if self._sparse:
            return [int(self.trie.max_fanout[d]) for d in range(nd)]
        return [self.cfg.vocab_size] * nd

    # ------------------------------------------------------------ prefill
    def prefill(self, params, tokens: jax.Array, lengths: jax.Array,
                dtype=jnp.float32) -> Tuple[jax.Array, SeparatedCache]:
        """tokens (R, S) right-padded; lengths (R,).  Returns (logits (R,V),
        separated cache with the shared side installed)."""
        R, S = tokens.shape
        cache0 = self.model.init_cache(R, S, dtype)
        logits, filled = self.model.prefill(
            params, {"tokens": tokens, "lengths": lengths}, cache0)
        sep = init_separated_cache(self.cfg, self.gr, R, S, dtype)
        sep = write_prefill(sep, filled["dense"]["k"], filled["dense"]["v"],
                            lengths)
        return logits, sep

    # ----------------------------------------------------- staged prefill
    def _chunk_forward(self, params, tokens: jax.Array, offsets: jax.Array,
                       lengths: jax.Array, S: int, kv_xs: tuple,
                       view, store) -> Tuple[jax.Array, tuple]:
        """Shared staged-prefill chunk forward (paper §5).

        The contiguous (``prefill_chunk``) and arena-paged
        (``prefill_chunk_paged``) variants run the SAME transformer block;
        they differ only in where the prior shared KV lives and where this
        chunk's KV is written, abstracted here as two per-layer callbacks
        over the scanned KV store ``kv_xs``:

          view(kv)        -> contiguous (R, S, kvH, hd) k/v for attention
          store(kv, k, v) -> this layer's scan output (collected KV, or the
                             updated physical store)

        Each chunk query attends causally over the already-installed shared
        KV (positions < offset) plus the earlier positions of its own chunk
        — exactly the rows a monolithic prefill's causal mask exposes, so
        the result is equivalent position-by-position (the equivalence
        property tests lock this down).  Returns (logits (R, V) at each
        request's last valid chunk position, per-layer scan outputs)."""
        cfg = self.cfg
        R, C = tokens.shape
        x = params["embed"][tokens]                          # (R, C, d)
        hd = cfg.resolved_head_dim
        rot = int(hd * cfg.rope_fraction) & ~1
        pos = offsets[:, None] + jnp.arange(C)[None, :]      # (R, C) absolute
        cos, sin = rope_angles(pos, rot, cfg.rope_theta)
        scale = 1.0 / math.sqrt(hd)
        slot = chunk_slots(offsets, lengths, C, S)
        ridx = jnp.arange(R)[:, None]
        # causal over absolute positions: key slot p visible to chunk query i
        # iff p <= offset + i (prior chunks AND the intra-chunk prefix; slots
        # past the written frontier are masked, so stale contents are inert)
        vis = (jnp.arange(S)[None, None, :] <= pos[:, :, None]
               )[:, None, None, :, :]                        # (R,1,1,C,S)

        def layer_body(h, xs):
            lp, kv = xs[0], xs[1:]
            hn = apply_norm(lp["ln1"], h, cfg.norm_kind, cfg.norm_eps)
            q, k, v = gqa_qkv(lp["attn"], hn, cfg)
            if cfg.rope_kind == "rope":
                q = apply_rope(q, cos, sin, cfg.rope_fraction)
                k = apply_rope(k, cos, sin, cfg.rope_fraction)
            sk, sv = view(kv)
            sk = sk.at[ridx, slot].set(k.astype(sk.dtype), mode="drop")
            sv = sv.at[ridx, slot].set(v.astype(sv.dtype), mode="drop")
            a = mha(q, sk, sv, vis, scale)
            h = h + dense(a.reshape(R, C, -1), lp["attn"]["wo"])
            h = h + apply_mlp(lp["mlp"],
                              apply_norm(lp["ln2"], h, cfg.norm_kind,
                                         cfg.norm_eps), cfg.act_kind)
            return h, store(kv, k, v)

        x, ys = jax.lax.scan(layer_body, x,
                             (params["dense_layers"],) + kv_xs)
        x = apply_norm(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
        last = jnp.maximum(lengths - 1, 0)                   # len-0 guard
        x_last = x[jnp.arange(R), last]
        logits = self.model._logits(params, x_last).astype(jnp.float32)
        return logits, ys

    def prefill_chunk(self, params, tokens: jax.Array, offsets: jax.Array,
                      lengths: jax.Array, cache: SeparatedCache
                      ) -> Tuple[jax.Array, SeparatedCache]:
        """One staged-prefill chunk (paper §5 unified prefill/decode).

        tokens  : (R, C) chunk tokens, right-padded
        offsets : (R,) absolute start position of each request's chunk —
                  must equal the request's current ``shared_len``
        lengths : (R,) valid tokens in this chunk (0 = request not scheduled
                  this step; its cache passes through untouched)
        cache   : separated cache holding every previously-written chunk

        Returns (logits (R, V) at each request's last valid chunk position
        — meaningful only on its final chunk — and the cache with this
        chunk's KV installed and ``shared_len`` advanced to
        ``offsets + lengths``).  See :meth:`_chunk_forward`."""
        S = cache.shared_k.shape[2]
        logits, (ks, vs) = self._chunk_forward(
            params, tokens, offsets, lengths, S,
            (cache.shared_k, cache.shared_v),
            view=lambda kv: kv,                  # xs ARE the contiguous view
            store=lambda kv, k, v: (k, v))       # collect chunk KV as ys
        new_cache = write_prefill_chunk(cache, ks, vs, offsets, lengths)
        return logits, new_cache

    # ------------------------------------------------ arena-paged variants
    # Same computation as prefill_chunk / beam_phase, but the shared KV
    # lives in a paged arena (core/kv_arena.py): prior KV is read THROUGH
    # per-request page tables and chunk KV is scattered into the owning
    # request's pages.  The gather is a pure permutation of the same float
    # values and padding keys are masked to exact-zero contributions, so
    # both variants are bit-identical to the contiguous-cache path
    # (tests/test_pipelined.py).

    def prefill_chunk_paged(self, params, tokens: jax.Array,
                            offsets: jax.Array, lengths: jax.Array,
                            pages_k: jax.Array, pages_v: jax.Array,
                            table: jax.Array
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """One staged-prefill chunk over the paged shared-KV arena.

        tokens    : (R, C) chunk tokens, right-padded
        offsets   : (R,) absolute start position of each request's chunk
        lengths   : (R,) valid tokens in this chunk (0 = request skipped)
        pages_k/v : (L, P, kvH, pg, hd) physical page pool
        table     : (R, MP) int32 page tables (OOB sentinel for unmapped)

        Returns (logits (R, V) at each request's last valid chunk position,
        new_pages_k, new_pages_v) — the pool with this chunk's KV scattered
        into the owning requests' pages.  Same transformer block as
        :meth:`prefill_chunk` (see :meth:`_chunk_forward`); only the KV
        view (page-table gather) and the write target (physical pages,
        stale contents masked) differ."""
        P, pg = pages_k.shape[1], pages_k.shape[3]
        pid, pslot = page_slots(table, offsets, lengths,
                                tokens.shape[1], pg, P)

        def view(kv):
            pk, pv = kv                                      # (P,kvH,pg,hd)
            return (gather_pages(pk[None], table)[0],
                    gather_pages(pv[None], table)[0])

        def store(kv, k, v):
            # advanced indices around the head slice put the (R, C) index
            # dims first, so the update is k's own (R, C, kvH, hd)
            pk, pv = kv
            return (pk.at[pid, :, pslot].set(k.astype(pk.dtype), mode="drop"),
                    pv.at[pid, :, pslot].set(v.astype(pv.dtype), mode="drop"))

        logits, (nk, nv) = self._chunk_forward(
            params, tokens, offsets, lengths, table.shape[1] * pg,
            (pages_k, pages_v),
            view=view, store=store)
        return logits, nk, nv

    def beam_phase_paged(self, params, state: xbeam.BeamState,
                         parent: jax.Array, unshared_k: jax.Array,
                         unshared_v: jax.Array, pages_k: jax.Array,
                         pages_v: jax.Array, table: jax.Array,
                         shared_len: jax.Array, d: int
                         ) -> Tuple[xbeam.BeamState, jax.Array,
                                    jax.Array, jax.Array]:
        """Decode phase ``d`` attending through page tables.

        With ``attention_impl="kernel"`` the fused paged Pallas kernel reads
        the pool tile-by-tile through the scalar-prefetched page table — no
        contiguous (R, S, kvH, hd) view is ever materialized (DESIGN.md
        §11).  Otherwise the group's shared KV is gathered from the arena
        into the contiguous view a :class:`SeparatedCache` holds and the
        ordinary :meth:`beam_phase` runs.  Either way it is one dispatch
        for the whole same-phase group.  Returns
        (state, parent, unshared_k, unshared_v)."""
        if self.attention_impl == "kernel":
            logits, uk, uv = self.decode_step_paged(
                params, state.tokens[:, :, d - 1], parent, pages_k, pages_v,
                table, shared_len, unshared_k, unshared_v, jnp.int32(d - 1))
            state, parent = self._beam_select(state, logits, d)
            return state, parent, uk, uv
        cache = SeparatedCache(
            shared_k=gather_pages(pages_k, table),
            shared_v=gather_pages(pages_v, table),
            shared_len=shared_len,
            unshared_k=unshared_k, unshared_v=unshared_v,
            step=jnp.int32(d - 1))
        state, parent, cache = self.beam_phase(params, state, parent,
                                               cache, d)
        return state, parent, cache.unshared_k, cache.unshared_v

    # -------------------------------------------------------- decode phase
    def _attend(self, q, sk, sv, slen, uk, uv, dstep):
        if self.attention_impl == "paged":
            return paged_beam_attention(q, sk, sv, slen, uk, uv, dstep)
        if self.attention_impl == "kernel":
            from repro.kernels.beam_attn.ops import beam_attention
            return beam_attention(q, sk, sv, slen, uk, uv, dstep)
        return staged_beam_attention(q, sk, sv, slen, uk, uv, dstep)

    def _decode_forward(self, params, prev_tokens: jax.Array,
                        parent: jax.Array, kv_xs: tuple, attend,
                        shared_len: jax.Array, dstep: jax.Array,
                        unshared_k: jax.Array, unshared_v: jax.Array
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Shared decode-phase transformer body (one token per beam).

        ``kv_xs`` are per-layer scanned arrays holding the shared KV in
        whatever physical form the caller keeps it — contiguous
        (L, R, S, kvH, hd) slices or (L, P, kvH, pg, hd) arena pools —
        and ``attend(q, shared_layer_kv, uk, uv)`` computes attention
        against that form (``shared_layer_kv`` is the per-layer slice tuple
        of ``kv_xs``).  Returns (logits (R, BW, V), forked+appended
        unshared_k/v)."""
        cfg = self.cfg
        R, BW = prev_tokens.shape
        x = params["embed"][prev_tokens]         # (R, BW, d)
        hd = cfg.resolved_head_dim
        rot = int(hd * cfg.rope_fraction) & ~1
        pos = (shared_len + dstep)[:, None]                # (R,1)
        cos, sin = rope_angles(pos, rot, cfg.rope_theta)
        n_kv = len(kv_xs)

        def layer_body(h, xs):
            lp = xs[0]
            skv = xs[1:1 + n_kv]
            uk, uv = xs[1 + n_kv], xs[2 + n_kv]
            hn = apply_norm(lp["ln1"], h, cfg.norm_kind, cfg.norm_eps)
            q, k, v = gqa_qkv(lp["attn"], hn, cfg)
            if cfg.rope_kind == "rope":
                q = apply_rope(q, cos, sin, cfg.rope_fraction)
                k = apply_rope(k, cos, sin, cfg.rope_fraction)
            # fork (gather by parent) + token-granularity append at dstep
            idx = parent[:, :, None, None, None]
            uk = jnp.take_along_axis(uk, idx, axis=1)
            uv = jnp.take_along_axis(uv, idx, axis=1)
            uk = jax.lax.dynamic_update_slice_in_dim(
                uk, k[:, :, None].astype(uk.dtype), dstep, axis=2)
            uv = jax.lax.dynamic_update_slice_in_dim(
                uv, v[:, :, None].astype(uv.dtype), dstep, axis=2)
            a = attend(q, skv, uk, uv)
            h = h + dense(a.reshape(R, BW, -1), lp["attn"]["wo"])
            h = h + apply_mlp(lp["mlp"],
                              apply_norm(lp["ln2"], h, cfg.norm_kind,
                                         cfg.norm_eps), cfg.act_kind)
            return h, (uk, uv)

        x, (uk, uv) = jax.lax.scan(
            layer_body, x,
            (params["dense_layers"],) + tuple(kv_xs)
            + (unshared_k, unshared_v))
        x = apply_norm(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
        logits = self.model._logits(params, x).astype(jnp.float32)
        return logits, uk, uv

    def decode_step(self, params, prev_tokens: jax.Array, parent: jax.Array,
                    cache: SeparatedCache
                    ) -> Tuple[jax.Array, SeparatedCache]:
        """One decode phase.

        prev_tokens : (R, BW) tokens selected by the preceding beam phase
        parent      : (R, BW) beam fork indices from that phase
        Returns (logits (R, BW, V), updated cache)."""
        dstep = cache.step                       # unshared slot to write

        def attend(q, skv, uk, uv):
            return self._attend(q, skv[0], skv[1], cache.shared_len,
                                uk, uv, dstep)

        logits, uk, uv = self._decode_forward(
            params, prev_tokens, parent, (cache.shared_k, cache.shared_v),
            attend, cache.shared_len, dstep, cache.unshared_k,
            cache.unshared_v)
        new_cache = dataclasses.replace(cache, unshared_k=uk, unshared_v=uv,
                                        step=dstep + 1)
        return logits, new_cache

    def decode_step_paged(self, params, prev_tokens: jax.Array,
                          parent: jax.Array, pages_k: jax.Array,
                          pages_v: jax.Array, table: jax.Array,
                          shared_len: jax.Array, unshared_k: jax.Array,
                          unshared_v: jax.Array, dstep: jax.Array
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """One decode phase reading the shared prefix straight out of the
        arena page pool via the fused paged Pallas kernel (DESIGN.md §11).

        pages_k/v : (L, P, kvH, pg, hd) physical page pool (the layer axis
                    is scanned, so the kernel sees one (P, kvH, pg, hd)
                    slice per layer)
        table     : (R, MP) int32 page tables (OOB sentinel for unmapped)
        Returns (logits (R, BW, V), forked+appended unshared_k/v)."""
        from repro.kernels.beam_attn.ops import arena_beam_attention_kernel

        def attend(q, skv, uk, uv):
            return arena_beam_attention_kernel(q, skv[0], skv[1], table,
                                               shared_len, uk, uv, dstep)

        return self._decode_forward(params, prev_tokens, parent,
                                    (pages_k, pages_v), attend, shared_len,
                                    dstep, unshared_k, unshared_v)

    # ------------------------------------------------- stepwise decode API
    # One beam phase at a time, so the serving engine can interleave decode
    # steps of in-flight requests with prefill chunks of arriving ones
    # (continuous batching).  Masks are device-resident (graph-mode path).

    def beam_phase0(self, logits0: jax.Array
                    ) -> Tuple[xbeam.BeamState, jax.Array]:
        """First beam expansion from prefill logits (R, V) — the TTFT point:
        the request has produced its first scored continuations."""
        gr = self.gr
        R = logits0.shape[0]
        state = xbeam.init_beam_state(R, gr)
        logits = jnp.broadcast_to(logits0[:, None, :],
                                  (R, gr.beam_width, self.cfg.vocab_size))
        if self._sparse:
            toks, cids = self.trie.device_children(0)
            return xbeam.sparse_beam_step(state, logits, toks, cids, gr)
        mask0 = (self.trie.device_mask0()[None, None]
                 if self.trie is not None else jnp.float32(0.0))
        return xbeam.beam_step(state, logits, mask0, gr)

    def _beam_select(self, state: xbeam.BeamState, logits: jax.Array,
                     d: int) -> Tuple[xbeam.BeamState, jax.Array]:
        """Phase-``d`` beam expansion over fresh decode logits: sparse
        trie-gather or dense mask-and-sort, per ``GRConfig.beam_select``."""
        if self._sparse:
            toks, cids = self.trie.device_children(d)
            return xbeam.sparse_beam_step(state, logits, toks, cids, self.gr)
        if self.trie is not None:
            mask = self.trie.device_masks(d, state.tokens[:, :, :d])
        else:
            mask = jnp.float32(0.0)
        return xbeam.beam_step(state, logits, mask, self.gr)

    def beam_phase(self, params, state: xbeam.BeamState, parent: jax.Array,
                   cache: SeparatedCache, d: int
                   ) -> Tuple[xbeam.BeamState, jax.Array, SeparatedCache]:
        """Decode phase ``d`` (1..ND-1): one decode forward + beam step.

        Sparse mode reuses ``state.prefix_ids`` (threaded by the previous
        phase's select) — one CSR table row lookup instead of re-walking
        the trie over the d-token prefixes."""
        logits, cache = self.decode_step(params, state.tokens[:, :, d - 1],
                                         parent, cache)
        state, parent = self._beam_select(state, logits, d)
        return state, parent, cache

    def decode_from_prefill(self, params, logits0: jax.Array,
                            cache: SeparatedCache) -> Dict[str, jax.Array]:
        """Full beam generation over an already-prefilled separated cache
        (monolithic or chunked — the equivalence tests compare both)."""
        state, parent = self.beam_phase0(logits0)
        for d in range(1, self.gr.num_decode_phases):
            state, parent, cache = self.beam_phase(params, state, parent,
                                                   cache, d)
        out = {"items": state.tokens, "log_probs": state.log_probs}
        if state.pruned is not None:
            out["pruned"] = state.pruned
        return out

    # ------------------------------------------------------------ generate
    def backend(self, mode: str) -> "ExecutionBackend":
        """Cached :class:`ExecutionBackend` for ``mode`` ("graph"|"eager")."""
        if mode not in self._backends:
            self._backends[mode] = make_backend(mode, self)
        return self._backends[mode]

    def generate(self, params, tokens: jax.Array, lengths: jax.Array,
                 mode: str = "graph", dtype=jnp.float32,
                 workspace=None) -> Dict[str, jax.Array]:
        """Full GR inference for a batch of R requests.

        mode='graph': single jitted program, device-resident masks.
        mode='eager': per-phase dispatch with host (numpy) mask generation.
        Returns {"items": (R,BW,ND) int32, "log_probs": (R,BW) f32}."""
        out, _ = self.backend(mode).execute(params, tokens, lengths,
                                            dtype=dtype, workspace=workspace)
        return out

    @functools.partial(jax.jit, static_argnums=(0,), static_argnames=("dtype",))
    def _generate_graph(self, params, tokens, lengths, dtype=jnp.float32):
        # one fused program: prefill + the same stepwise phase chain the
        # continuous engine drives (dense masks or sparse trie-gather,
        # selected by GRConfig.beam_select)
        logits0, cache = self.prefill(params, tokens, lengths, dtype)
        return self.decode_from_prefill(params, logits0, cache)


# ---------------------------------------------------------------------------
# Execution backends (ISSUE 1 tentpole)
#
# One interface for the graph/eager split: a backend owns its compile cache,
# warmup, and (eager) mask workspace, executes a padded batch, and returns
# (outputs, timing).  The serving engine and ``GRDecoder.generate`` both go
# through this interface — there is exactly one implementation of each
# dispatch mode in the codebase.
# ---------------------------------------------------------------------------

#: timing keys every backend returns (seconds, except ``dispatches``)
TIMING_KEYS = ("device_s", "host_mask_s", "critical_s", "compile_s",
               "dispatches")


@runtime_checkable
class ExecutionBackend(Protocol):
    """Executes one padded request batch end-to-end."""

    name: str

    def execute(self, params, tokens: jax.Array, lengths: jax.Array,
                dtype=jnp.float32, workspace=None
                ) -> Tuple[Dict[str, jax.Array], Dict[str, float]]:
        """Returns ({"items", "log_probs"}, timing dict over TIMING_KEYS).

        ``critical_s`` is the simulated-clock batch duration (host mask work
        may overlap the device forward; see DESIGN.md §4)."""
        ...


def _place_batch(mesh, tokens, lengths):
    """Commit a padded request batch to a replica's mesh slice (DESIGN.md
    §10).  Without a mesh the arrays stay uncommitted — today's exact
    single-device staging.  With one, input_pspecs places them so the jitted
    program runs on the replica's devices instead of pulling everything to
    the process default device."""
    if mesh is None:
        return tokens, lengths
    from repro.sharding.specs import place_inputs
    return place_inputs((jnp.asarray(tokens), jnp.asarray(lengths)), mesh)


class GraphBackend:
    """Whole generate loop as ONE jitted XLA program per shape bucket.

    Kernel-graph capture analogue: a single host->device dispatch per batch
    with device-resident masks (paper §7 + §9.5)."""

    name = "graph"

    def __init__(self, decoder: "GRDecoder", mesh=None):
        self.decoder = decoder
        self.mesh = mesh
        self._warm: set = set()

    def execute(self, params, tokens, lengths, dtype=jnp.float32,
                workspace=None):
        del workspace                      # graph mode: masks live on device
        tokens, lengths = _place_batch(self.mesh, tokens, lengths)
        key = (tuple(tokens.shape), jnp.dtype(dtype).name)
        compile_s = 0.0
        if key not in self._warm:
            t0 = time.perf_counter()
            self.decoder._generate_graph(params, tokens, lengths, dtype=dtype
                                         )["items"].block_until_ready()
            compile_s = time.perf_counter() - t0
            self._warm.add(key)
        t0 = time.perf_counter()
        out = self.decoder._generate_graph(params, tokens, lengths,
                                           dtype=dtype)
        out["items"].block_until_ready()
        dt = time.perf_counter() - t0
        return out, {"device_s": dt, "host_mask_s": 0.0, "critical_s": dt,
                     "compile_s": compile_s, "dispatches": 1}


class EagerBackend:
    """Per-phase dispatch with host-side (numpy) mask generation.

    ``host_overlap`` models xSchedule's overlap of host mask generation with
    the device forward pass: the effective critical path per phase is
    max(device_time, host_mask_time) instead of their sum.

    With ``beam_select="sparse"`` there is no host mask work at all: the
    per-phase beam step gathers from the trie's device-resident CSR child
    tables (``host_mask_s`` stays 0 and the workspace is never touched)."""

    name = "eager"

    def __init__(self, decoder: "GRDecoder", host_overlap: bool = False,
                 capacity_hint: int = 0, mesh=None):
        self.decoder = decoder
        self.host_overlap = host_overlap
        self.capacity_hint = capacity_hint
        self.mesh = mesh
        self._cache: Dict[tuple, tuple] = {}   # shape key -> jitted fns
        self._workspace: Optional[MaskWorkspace] = None

    def _programs(self, params, tokens, lengths, dtype):
        """Per-shape jitted (prefill, step, bstep), warmed on first use."""
        dec, gr, cfg = self.decoder, self.decoder.gr, self.decoder.cfg
        key = (tuple(tokens.shape), jnp.dtype(dtype).name)
        compile_s = 0.0
        if key not in self._cache:
            t0 = time.perf_counter()
            prefill = jax.jit(lambda p, t, l: dec.prefill(p, t, l, dtype))
            step = jax.jit(dec.decode_step, donate_argnums=(3,))
            if dec._sparse:
                bstep = jax.jit(functools.partial(xbeam.sparse_beam_step,
                                                  gr=gr))
            else:
                bstep = jax.jit(functools.partial(xbeam.beam_step, gr=gr))
            # warm the full phase chain — including every mask/table shape
            # bstep will see — so steady-state calls never compile
            R = tokens.shape[0]
            V = cfg.vocab_size
            lo, ca = prefill(params, tokens, lengths)
            st = xbeam.init_beam_state(R, gr)
            lo2 = jnp.broadcast_to(lo[:, None, :], (R, gr.beam_width, V))
            if dec._sparse:
                st2, par = bstep(st, lo2, *dec.trie.device_children(0))
                warm = st2
                for d in range(1, gr.num_decode_phases):
                    warm, _ = bstep(warm, lo2, *dec.trie.device_children(d))
            elif dec.trie is None:
                st2, par = bstep(st, lo2, jnp.zeros((), jnp.float32))
            else:
                st2, par = bstep(st, lo2,
                                 jnp.zeros((1, 1, V), jnp.float32))
                bstep(st2, lo2,
                      jnp.zeros((R, gr.beam_width, V), jnp.float32))
            step(params, st2.tokens[:, :, 0], par, ca)
            compile_s = time.perf_counter() - t0
            self._cache[key] = (prefill, step, bstep)
        return self._cache[key] + (compile_s,)

    def _get_workspace(self, R: int, workspace=None) -> MaskWorkspace:
        if workspace is not None:
            return workspace
        gr, cfg = self.decoder.gr, self.decoder.cfg
        if self._workspace is None or self._workspace.buf.shape[0] < R:
            self._workspace = MaskWorkspace(max(R, self.capacity_hint),
                                            gr.beam_width, cfg.vocab_size)
        return self._workspace

    def execute(self, params, tokens, lengths, dtype=jnp.float32,
                workspace=None):
        dec = self.decoder
        gr, cfg, trie = dec.gr, dec.cfg, dec.trie
        sparse = dec._sparse
        tokens, lengths = _place_batch(self.mesh, tokens, lengths)
        R = tokens.shape[0]
        prefill, step, bstep, compile_s = self._programs(
            params, tokens, lengths, dtype)
        ws = self._get_workspace(R, workspace) \
            if (trie is not None and not sparse) else None

        device_s = host_s = critical_s = 0.0
        dispatches = 0

        t0 = time.perf_counter()
        logits0, cache = prefill(params, tokens, lengths)
        logits0.block_until_ready()
        dt = time.perf_counter() - t0
        device_s += dt
        critical_s += dt
        dispatches += 1

        state = xbeam.init_beam_state(R, gr)
        logits = jnp.broadcast_to(logits0[:, None, :],
                                  (R, gr.beam_width, cfg.vocab_size))
        if sparse:
            state, parent = bstep(state, logits, *trie.device_children(0))
        else:
            if trie is not None:
                mask = jnp.asarray(trie.host_masks(0, None))[None, None]
            else:
                mask = jnp.zeros((), jnp.float32)
            state, parent = bstep(state, logits, mask)
        for d in range(1, gr.num_decode_phases):
            t0 = time.perf_counter()
            logits, cache = step(params, state.tokens[:, :, d - 1],
                                 parent, cache)
            logits.block_until_ready()
            dev_dt = time.perf_counter() - t0
            dispatches += 1

            th = 0.0
            if trie is not None and not sparse:
                t0 = time.perf_counter()
                prefix = np.asarray(state.tokens[:, :, :d])
                if d == gr.num_decode_phases - 1:
                    m = ws.sparse_update(trie, d, prefix)
                else:
                    m = ws.dense_fill(trie, d, prefix)
                mask = jnp.asarray(m)
                th = time.perf_counter() - t0
            device_s += dev_dt
            host_s += th
            # paper §7: mask generation overlaps the device forward
            critical_s += max(dev_dt, th) if self.host_overlap \
                else dev_dt + th
            t0 = time.perf_counter()
            if sparse:
                state, parent = bstep(state, logits,
                                      *trie.device_children(d))
            else:
                state, parent = bstep(state, logits, mask)
            bs_dt = time.perf_counter() - t0
            device_s += bs_dt
            critical_s += bs_dt
            dispatches += 1
        out = {"items": state.tokens, "log_probs": state.log_probs}
        if state.pruned is not None:
            out["pruned"] = state.pruned
        return out, {"device_s": device_s, "host_mask_s": host_s,
                     "critical_s": critical_s, "compile_s": compile_s,
                     "dispatches": dispatches}


def make_backend(name: str, decoder: GRDecoder, host_overlap: bool = False,
                 capacity_hint: int = 0, mesh=None) -> ExecutionBackend:
    """Backend factory: the ONLY place a dispatch-mode name is interpreted.

    ``mesh`` pins the backend's batches to a replica's device-mesh slice;
    None keeps the process-default device (single-device serving)."""
    if name == "graph":
        return GraphBackend(decoder, mesh=mesh)
    if name == "eager":
        return EagerBackend(decoder, host_overlap=host_overlap,
                            capacity_hint=capacity_hint, mesh=mesh)
    raise ValueError(f"unknown execution backend {name!r}; "
                     f"have ['graph', 'eager']")
