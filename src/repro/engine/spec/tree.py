"""Token-TREE self-speculative decoding (DESIGN.md §8).

Chain drafting (engine/spec/drafter.py) wastes the verify dispatch on
every rejected suffix: one wrong token kills the whole tail. A token
TREE spends the same T = N+1 verify budget on top-k *branches* per draft
step — the target only has to match ONE of each node's children for the
walk to continue, so expected accepted length per verify dispatch rises
whenever the drafter's top-1 is unsure but its top-k covers the target.

Layout: the tree is flattened in BFS order into one block of N+1 tokens
(slot 0 = the pending token = the root; level ℓ's nodes contiguous,
children of a node contiguous). The block is written at cache positions
``pos .. pos + N`` — storage is slot-sequential, but RoPE runs at each
token's tree DEPTH and attention at its ANCESTOR BITMAP (bit i of
``anc[j]`` = BFS slot i on j's root path), so a node's K/V is rotated
for exactly the position it would hold in sequential decode and the
accepted root-to-leaf path can be *compacted* into the leading slots by
pure page-slot moves — no re-rotation, no page churn
(:func:`compact_accepted`).

One round = D+1 dispatches for 1..D+1 tokens (D = tree depth):

    draft:  1 root call + D-1 frontier calls (level ℓ feeds its n_ℓ
            nodes as one tree-attention block; top-f_ℓ expansion stays
            on device)
    verify: ONE T = N+1 tree-attention call with the target params;
            ``sampling.tree_verify`` walks the longest accepted path
            with sibling-set rejection sampling (lossless), then the
            path's K/V is compacted and the position advances by
            ``n_new`` — the rejected branches rewind by position only,
            exactly the chain invariant (DESIGN.md §4.2).

A chain is the fanout-all-1 special case and is bit-identical to the
PR 2 chain spec path (pinned by ``tests/test_spec_tree.py``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.sampling import SamplingParams, tree_verify
from repro.models.registry import get_model

# ancestor bitmaps ride in int32 lanes (kernel + jnp mask shift by the
# in-window offset), so a tree block can hold at most 31 fed tokens
MAX_TREE_TOKENS = 31


class TreeTemplate:
    """Static shape of a draft token tree: fanout per depth, BFS flat
    indexing, parent/child maps and per-node ancestor bitmaps.

    ``fanout = (4, 2, 2)`` means the root proposes 4 children, each of
    those 2, each of those 2 — 28 nodes, 16 leaves, depth 3, and a
    T = 29 verify block. ``(k,) * 1`` / ``(1,) * K`` are chains.
    """

    def __init__(self, fanout: Tuple[int, ...]):
        if not fanout or any(f < 1 for f in fanout):
            raise ValueError(f"fanout must be positive per depth: {fanout}")
        self.fanout = tuple(int(f) for f in fanout)
        self.depth = len(self.fanout)
        sizes = []
        n = 1
        for f in self.fanout:
            n *= f
            sizes.append(n)
        self.level_sizes = tuple(sizes)            # nodes per level 1..D
        self.n_nodes = sum(sizes)                  # N (root excluded)
        if self.n_nodes + 1 > MAX_TREE_TOKENS:
            raise ValueError(
                f"tree {fanout} needs {self.n_nodes + 1} fed tokens "
                f"(> {MAX_TREE_TOKENS}: ancestor bitmaps are int32)")
        # level_starts[ℓ] = BFS flat index of level ℓ's first node
        starts = [0, 1]
        for s in sizes[:-1]:
            starts.append(starts[-1] + s)
        self.level_starts = tuple(starts)          # length D + 1
        n1 = self.n_nodes + 1
        self.depths = np.zeros(n1, np.int32)
        self.parents = np.full(n1, -1, np.int32)
        self.child_start = np.full(n1, -1, np.int32)
        self.anc = np.zeros(n1, np.int32)
        self.anc[0] = 1                            # root sees itself
        for lvl in range(1, self.depth + 1):
            st, sz = self.level_starts[lvl], sizes[lvl - 1]
            f_in = self.fanout[lvl - 1]            # branching INTO lvl
            for m in range(sz):
                i = st + m
                self.depths[i] = lvl
                self.parents[i] = (0 if lvl == 1
                                   else self.level_starts[lvl - 1]
                                   + m // f_in)
                self.anc[i] = self.anc[self.parents[i]] | (1 << i)
        for lvl in range(1, self.depth):           # child maps (non-leaf)
            st, sz = self.level_starts[lvl], sizes[lvl - 1]
            f_out = self.fanout[lvl]
            for m in range(sz):
                self.child_start[st + m] = self.level_starts[lvl + 1] \
                    + m * f_out
        self.child_start[0] = 1

    def level_tree(self, lvl: int) -> dict:
        """The ``decode_step(tree=...)`` spec for feeding level ``lvl``'s
        nodes: window covers every BFS slot written so far."""
        st, sz = self.level_starts[lvl], self.level_sizes[lvl - 1]
        return {"depths": jnp.asarray(self.depths[st:st + sz]),
                "anc": jnp.asarray(self.anc[st:st + sz]),
                "window": st + sz, "start": st}

    def verify_tree(self) -> dict:
        """The spec for the full T = N+1 verify block."""
        return {"depths": jnp.asarray(self.depths),
                "anc": jnp.asarray(self.anc),
                "window": self.n_nodes + 1, "start": 0}


def build_tree_draft_fn(cfg, api, use_pallas: bool, tpl: TreeTemplate,
                        draft_layers: Optional[int] = None):
    """Returns draft_fn(draft_params, cache, tokens, positions,
    block_tables, max_live) -> tree tokens [B, N] (BFS order).

    Level-by-level greedy top-k expansion: the root call is a plain
    decode step; level ℓ's n_ℓ nodes are then fed as ONE tree-attention
    block (each node attends to the committed prefix + its own root
    path) and each node's logits propose its top-f_ℓ children — distinct
    by construction, which is what makes the verify's sibling-set
    rejection sampling exact. Like the chain drafter, draft K/V written
    into the shared pool never survives the round (the verify re-writes
    every fed slot with target K/V) and the whole expansion is unrolled
    at trace time, so a draft round costs D dispatches regardless of
    tree width.
    """
    dl = draft_layers if draft_layers is not None else cfg.n_layers
    dcfg = dataclasses.replace(cfg, n_layers=dl) if dl != cfg.n_layers \
        else cfg

    def draft_fn(draft_params, cache, tokens, positions, block_tables,
                 max_live=None):
        # trace-time-only phase name for device profiler alignment
        # (telemetry, DESIGN.md §10)
        with jax.named_scope("spec_tree_draft"):
            dcache = jax.tree_util.tree_map(lambda c: c[:dl], cache) \
                if dl != cfg.n_layers else cache
            logits, dcache = api.decode_step(
                draft_params, dcache, tokens[:, None], positions, dcfg,
                None, use_pallas, block_tables=block_tables,
                max_live_pages=max_live)
            levels = []
            for lvl, f in enumerate(tpl.fanout):
                _, top = jax.lax.top_k(logits, f)   # [B, n_prev, f]
                toks = top.reshape(top.shape[0], -1).astype(jnp.int32)
                levels.append(toks)                 # level lvl+1 tokens
                if lvl + 1 == tpl.depth:
                    break
                spec = tpl.level_tree(lvl + 1)
                logits, dcache = api.decode_step(
                    draft_params, dcache, toks,
                    positions + spec["start"], dcfg, None, use_pallas,
                    block_tables=block_tables, max_live_pages=max_live,
                    tree=spec)
            return jnp.concatenate(levels, axis=1)

    return draft_fn


def compact_accepted(cache, block_tables, positions, path, n_new,
                     page_size: int):
    """Move the accepted root-to-leaf path's K/V into the leading slots.

    The verify writes target K/V for every fed tree slot at cache
    positions ``pos + i`` (BFS slot i); sequential decode would have the
    i-th *accepted* token at ``pos + i``-th... position ``pos + i`` of
    the PATH. ``path [B, D]`` holds the accepted nodes' BFS slots, so
    token i of the path moves ``pos + path[:, i] -> pos + 1 + i``. K was
    RoPE-rotated at tree depth == its final position, so the move is a
    pure gather/scatter through the block tables: reads happen before
    writes (functional update), sources are at-or-right-of their
    destinations (``path[:, i] >= i + 1``), and rows past the accepted
    length scatter to the page-id sentinel and are dropped — inactive
    slots and rejected branches never touch a page (rewind stays
    positional, DESIGN.md §4.2).
    """
    b, dmax = path.shape
    i = jnp.arange(dmax, dtype=jnp.int32)[None, :]
    valid = i < (n_new[:, None] - 1)               # accepted drafts only
    src_pos = positions[:, None] + jnp.maximum(path, 1)
    dst_pos = positions[:, None] + 1 + i
    num_pages = jax.tree_util.tree_leaves(cache)[0].shape[2]
    src_page = jnp.take_along_axis(block_tables, src_pos // page_size,
                                   axis=1)
    src_off = src_pos % page_size
    dst_page = jnp.where(
        valid, jnp.take_along_axis(block_tables, dst_pos // page_size,
                                   axis=1), num_pages)
    dst_off = dst_pos % page_size

    def move(buf):                                 # [L, KH, P, ps, X]
        vals = buf[:, :, src_page, src_off]        # [L, KH, B, D, X]
        return buf.at[:, :, dst_page, dst_off].set(vals, mode="drop")

    return jax.tree_util.tree_map(move, cache)


def build_tree_verify_fn(cfg, api, sampling: SamplingParams,
                         use_pallas: bool, tpl: TreeTemplate):
    """Returns verify_fn(params, cache, tokens, tree_tokens, positions,
    block_tables, active, remaining, rng, max_live) ->
    (out [B, D+1], n_new [B], tokens', positions', remaining', cache,
    rng) — the tree analogue of ``spec/verify.py:build_verify_fn``:
    same signature shape, same device-side budget clamps, plus the
    accepted-path KV compaction."""

    def verify_fn(params, cache, tokens, tree_tokens, positions,
                  block_tables, active, remaining, rng, max_live=None):
        # trace-time-only phase names for device profiler alignment
        # (telemetry, DESIGN.md §10)
        with jax.named_scope("spec_tree_verify"):
            feed = jnp.concatenate([tokens[:, None], tree_tokens], axis=1)
            logits, cache = api.decode_step(
                params, cache, feed, positions, cfg, None, use_pallas,
                block_tables=block_tables, max_live_pages=max_live,
                tree=tpl.verify_tree())
            rng, sub = jax.random.split(rng)
            n_acc, out, path = tree_verify(logits, feed, tpl.fanout,
                                           tpl.child_start, sub, sampling)
            n_new = jnp.minimum(n_acc + 1, remaining) * active  # [B]
            nxt = jnp.take_along_axis(
                out, jnp.maximum(n_new - 1, 0)[:, None], axis=1)[:, 0]
            tokens = jnp.where(n_new > 0, nxt, tokens)
            # leaves are [L, KH, P, ps, X] for every paged layout (K/V
            # pools or the MLA latent pool) — compaction is the same
            # block-table move
            page_size = jax.tree_util.tree_leaves(cache)[0].shape[3]
            with jax.named_scope("tree_compact"):
                cache = compact_accepted(cache, block_tables, positions,
                                         path, n_new, page_size)
            positions = positions + n_new
            remaining = remaining - n_new
        return out, n_new, tokens, positions, remaining, cache, rng

    return verify_fn


@functools.lru_cache(maxsize=32)
def tree_step_fns(cfg, sampling: SamplingParams, use_pallas: bool,
                  fanout: Tuple[int, ...],
                  draft_layers: Optional[int] = None):
    """Jitted (draft_fn, verify_fn, template) triple, memoized per (model
    config, sampling, backend, fanout, draft depth) — the adaptive
    controller flips between fanout profiles without recompiling."""
    api = get_model(cfg)
    tpl = TreeTemplate(fanout)
    draft_fn = build_tree_draft_fn(cfg, api, use_pallas, tpl, draft_layers)
    verify_fn = build_tree_verify_fn(cfg, api, sampling, use_pallas, tpl)
    return (jax.jit(draft_fn, static_argnums=(5,)),
            jax.jit(verify_fn, static_argnums=(9,)), tpl)
