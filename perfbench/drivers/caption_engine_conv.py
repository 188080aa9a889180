"""Drives a ``CaptionEngine`` that serves a hybrid decoder whose recurrent layers
are gated SHORT CONVOLUTIONS (LFM2: the recurrent store is the convolutions'
tails and nothing else) beside GQA layers, every layer past the leading dense
ones over sparse experts with a sorted dispatch that holds EVERY expert (the
first of five pipeline stages: nothing of a layer is left out), as the same
offline batch as ``drivers/caption_engine_kda.py``: that driver's decode warmer
(the store and the held-assignment rider ride in the call) and its judges of a
router and of a root-mean-square; the delta driver's spies, scope reading and
handing of a first token to the engine's own XLA path; the hybrid driver's
``_serve``; the indexed driver's bounded ramp (``DigestLoop``: one turnover of
the slots); the windowed driver's seeded parameters (a selection bias that is
not zero) and its programs' device seconds are imported. What is this driver's
own:

- the configuration file is checked against the flavor by its own keys
  (``conv_L_cache``, ``layer_types``, the router's counts, all experts held);
- the spies keep a ``check*`` request's TAILS (every conv layer's row of the
  store) after its prompt and after each of its decode programs;
- the spies also keep what a ``check*`` request's ROUTERS CHOSE: the flavor's
  programs hand out every token's experts in every sparse layer
  (``MoEConfig.hand_out_choice``), and a request's are put together from the
  shared prefix's build, its prefill chunks and its decode steps;
- ``correct`` holds EVERY layer. With all 64 experts held every layer's
  near-tie counts, a bfloat16 hidden state takes another expert than float32
  does at one (token, layer) in twelve, and one flip swaps a quarter of a
  layer's output, so the float32 reference FOLLOWS the program's choice (the
  section "correctness" below has the why) and every request is then held to
  bfloat16 rounding, not a median: first-step logits after prompts inside one
  prefill chunk and over three chunks with padding in the last, from the
  shared prefix's blocks AND tails snapshot, after each of 8 decode steps of
  six requests against the reference's one full forward; every conv layer's
  tails after each of them (the first layer's, below which nothing is routed,
  under a limit of its own); the choice itself, which must be the reference's
  own wherever its margin is wide; the program's router on the reference's own
  float32 hidden states; and the engine's own XLA path by the same judges. It
  is sized to a stated budget: one reference forward a request, in blocks (a
  layer at a time, upcast when its turn comes), every sequence of a group ONE
  seeded sequence of one length (the reference compiles three shapes);
- the traced slice is reduced twice (the paged kernels, ``gmm``), the conv mixer
  is timed by its scope (``mixer.short_conv``) and the programs' own device
  seconds are summed by kind.

``python -m perfbench.drivers.caption_engine_conv --lower-precision [router
activations tails stated]`` puts the reference itself, computing in fewer bits,
in the PROGRAM'S place in the same judges: the second of the two readings each
limit lies between (PERF.md). It exits 1 when a control comes out not
``correct``, as each of the three below the stated precisions must (``stated``,
bfloat16 activations alone, is what the file states and exits 0). ``--plant
[tables-swapped one-table kv-projection bias-lost final-norm]`` serves the same
checks from an engine whose parameters carry a fault above the first expert
layer while the reference's do not: what the judges tell from a program that
is wrong, not coarser. It exits 0 when every fault comes out not ``correct``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import time
from unittest import mock

import numpy as np

from perfbench import measure, trace_reduce
from perfbench.catalog import Cell, load_module
from perfbench.drivers import caption_engine_sparse as scoped
from perfbench.drivers.caption_engine import HOST_SPANS, KERNELS, reachable
from perfbench.drivers.caption_engine_hybrid import _serve
from perfbench.drivers.caption_engine_delta import hand_first_logits
from perfbench.drivers.caption_engine_kda import _judge_rms, _judge_router, _KdaPrivate, program_router
from perfbench.drivers.caption_engine_latent import EXPERT_KERNELS, _text_only
from perfbench.drivers.caption_engine_windowed import make_params, program_seconds
from perfbench.measure import annotate, log

REFERENCE = "lfm2_moe"
OWN_READERS = (
    "kernel.short_conv_time_share", "kernel.short_conv_hbm_share", "kernel.whole_moe_expert_matmul_roofline_share",
    "kernel.whole_moe_expert_time_share", "engine.conv_tail_gib", "engine.whole_moe_assignments_per_program",
    "kernel.whole_moe_cell_paged_decode_hbm_share", "engine.whole_moe_cell_prefill_device_share",
)
SCOPES = re.compile(r"mixer\.short_conv|moe\.route|moe\.experts")


# -- configuration ------------------------------------------------------------


def _program_config(cell: Cell, rehearse: bool):
    """(VLMConfig, kv_lanes, prefill_chunk, prefill_rows) as the program defines them."""
    from cosmos_curate_tpu.models.vlm import model as vlm_model

    conf = cell.config
    if rehearse:
        r = conf["rehearse"]
        lanes = tuple(map(tuple, r["kv_lanes"]))
        return getattr(vlm_model, r["preset"]), lanes, int(r["prefill_chunk"]), r.get("prefill_rows")
    flavor = vlm_model.vlm_flavor(conf["flavor"])
    check_config_file(conf, flavor.cfg, flavor.kv_lanes, flavor.prefill_rows)
    return flavor.cfg, flavor.kv_lanes, int(conf["serving"]["prefill_chunk"]), flavor.prefill_rows


def program_sizes(cfg) -> dict:
    """The flavor's sizes under the configuration file's (HF's) keys."""
    c, m = cfg.short_conv, cfg.moe
    return {
        "hidden_size": cfg.dim,
        "intermediate_size": int(round(cfg.dim * cfg.hidden_mult)),
        "num_hidden_layers": cfg.n_layers,
        "layer_types": list(cfg.layer_types),
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab,
        "max_position_embeddings": cfg.max_seq,
        "norm_eps": cfg.rms_eps,
        "rope_parameters": {"rope_theta": int(cfg.rope_theta), "rope_type": "default"},
        "conv_L_cache": c.l_cache,
        "conv_bias": False,  # the mixer has none (models/vlm/short_conv.py)
        "moe_intermediate_size": m.hidden,
        "num_experts": m.n_experts,
        "num_experts_per_tok": m.top_k,
        "num_dense_layers": m.first_dense,
        "norm_topk_prob": m.norm_topk_prob,
        "routed_scaling_factor": m.routed_scaling_factor,
        "use_expert_bias": m.selection_bias,
    }


def check_config_file(conf: dict, cfg, lanes, prefill_rows) -> None:
    """The file under ``configs/`` holds the sizes as run: refuse to measure a
    program whose flavor has moved away from it."""
    m = cfg.moe
    bad = {k: (conf[k], v) for k, v in program_sizes(cfg).items() if conf[k] != v}
    counts = conf["published_counts"]
    if counts["router_outputs"] != m.n_experts or list(counts["held_experts"]) != list(m.held_experts) or m.held is not None:
        bad["published_counts"] = (counts, (m.n_experts, m.held))
    # the points the config is silent on: the file's `assumed`, the program's fields
    assumed = conf["assumed"]
    program = {
        "tie_word_embeddings": cfg.tied_embeddings, "scoring_func": m.score_func, "selection_bias": m.selection_bias,
        "norm_topk_eps": m.norm_topk_eps, "router_precision": m.router_precision,
    }
    bad.update({f"assumed.{k}": (assumed[k], v) for k, v in program.items() if assumed[k] != v})
    block = (cfg.pre_norm, cfg.sandwich_norm, cfg.qk_norm, cfg.qk_norm_whole, cfg.qkv_bias, cfg.use_rope,
             cfg.attention_gate, m.shared_hidden, cfg.mla, cfg.indexer)
    if block != (True, False, True, False, False, True, False, 0, None, None):
        bad["assumed.block"] = (assumed["block"], block)
    if [list(l) for l in lanes] != conf["serving"]["kv_lanes"]:
        bad["kv_lanes"] = (conf["serving"]["kv_lanes"], lanes)
    if conf["serving"]["prefill_rows"] != prefill_rows:
        bad["prefill_rows"] = (conf["serving"]["prefill_rows"], prefill_rows)
    if bad:
        raise ValueError(f"configs/{conf['name']}.json (file, program) disagree: {bad}")


# -- the engine's private face ------------------------------------------------


class _ConvPrivate(_KdaPrivate):
    """``_KdaPrivate`` (the store and the held-assignment rider ride in the
    warmers' calls; a ``check*`` request's first logits, tokens and decode logits,
    the warmed programs and the prefill programs' valid tokens are kept) that
    also keeps a ``check*`` request's TAILS, every conv layer's row of the store
    ``[Lc, 2 * dim]``, after its prompt and after EACH of its decode programs
    (read off the store the program hands back), and, while ``keep_choice`` is
    set, WHAT ITS ROUTERS CHOSE: the flavor's programs hand out every token's
    experts in every sparse layer as their last output
    (``MoEConfig.hand_out_choice``), and a request's are put together from the
    shared prefix's build, its prefill chunks and its decode steps. (The state
    half the older spies read is of no width here.)"""

    def __init__(self, engine) -> None:
        super().__init__(engine)
        self.tails: dict[str, np.ndarray] = {}
        self.step_tails: dict[str, list[np.ndarray]] = {}
        self.keep_choice = False  # reading a program's choice waits for the program: `correct` alone does
        self.prefix_choice: np.ndarray | None = None  # [Ls, T, k] of the last prefix built
        self.chunks: dict[tuple, list] = {}  # (lane, slot) -> [(write index, valid, [Ls, T, k])] since the slot last started
        self.prompt_choice: dict[str, list] = {}  # a check* request's chunks
        self.step_choice: dict[str, list[np.ndarray]] = {}  # [Ls, k] a decode program
        start_slot, decode = engine._start_slot, engine._decode
        prefill_batch, run_prefill, prefix_prefill = engine._prefill_batch, engine._run_prefill, engine._prefix_prefill
        last = []

        def on_prefill_batch(*args):
            out = prefill_batch(*args)
            last[:] = [out[-1]]
            return out

        def on_run_prefill(lane, slots_arr, tables, embeds, write_index, t_valid, *rest):
            logits = run_prefill(lane, slots_arr, tables, embeds, write_index, t_valid, *rest)
            if self.keep_choice:  # (padding rows repeat row 0: the same chunk twice)
                choice = np.asarray(last[0])
                for j, slot_idx in enumerate(np.asarray(slots_arr)):
                    self.chunks.setdefault((lane.length, int(slot_idx)), []).append(
                        (int(write_index[j]), int(t_valid[j]), choice[:, j])
                    )
            return logits

        def on_prefix(*args):
            out = prefix_prefill(*args)
            if self.keep_choice:
                self.prefix_choice = np.asarray(out[-1])
            return out

        def on_start(lane, slot_idx, req, *rest):
            chunks = self.chunks.pop((lane.length, int(slot_idx)), [])  # the next tenant's start from nothing
            if req.request_id.startswith("check"):  # BEFORE the slot can finish and its row be claimed again
                self.tails[req.request_id] = np.asarray(engine._conv[:, int(engine._state_rows(lane, slot_idx))], np.float32)
                self.prompt_choice[req.request_id] = chunks
            return start_slot(lane, slot_idx, req, *rest)

        def on_decode(params, pool_k, pool_v, tables, *rest):
            out = decode(params, pool_k, pool_v, tables, *rest)
            for name, (lane, slot_idx) in self.place.items():  # (as the older spy keeps the step's logits)
                slot = lane.slots.get(slot_idx)
                if slot is not None and slot.request.request_id == name and tables.shape == lane.table.shape:
                    row = int(engine._state_rows(lane, slot_idx))
                    self.step_tails.setdefault(name, []).append(np.asarray(out[5][:, row], np.float32))
                    self.step_choice.setdefault(name, []).append(np.asarray(out[-1][:, slot_idx, 0]))
            return out

        engine._start_slot, engine._decode = on_start, on_decode
        engine._prefill_batch, engine._run_prefill, engine._prefix_prefill = on_prefill_batch, on_run_prefill, on_prefix

    def choice_of(self, name: str, n_prefix: int, n: int, steps: int = 0) -> np.ndarray | None:
        """``[Ls, n + steps, k]``: what request ``name``'s routers chose at its
        ``n`` prompt positions (the first ``n_prefix`` the shared prefix's
        build's) and its first ``steps`` decode steps; None where a position is
        in no program that was read."""
        chunks = self.prompt_choice.get(name) or []
        if not chunks or (n_prefix and self.prefix_choice is None):
            return None
        layers, _, k = chunks[0][2].shape
        choice = np.full((layers, n + steps, k), -1, np.int32)
        if n_prefix:
            choice[:, :n_prefix] = self.prefix_choice[:, :n_prefix]
        for at, valid, chunk in chunks:
            choice[:, at : at + valid] = chunk[:, :valid]
        for j, step in enumerate(self.step_choice.get(name, [])[:steps]):
            choice[:, n + j] = step
        return None if (choice < 0).any() else choice

    # (the older warmers unpack a program's outputs by count: these programs hand out one more)
    def warm_prefill(self, lane, rows: int, t: int) -> None:
        import jax.numpy as jnp

        e, cfg = self.e, self.e.cfg
        zeros = jnp.asarray(np.zeros(rows, np.int32))
        logits, e._pool_k, e._pool_v, e._ssm, e._conv, *_ = e._prefill_batch(
            e.params, e._pool_k, e._pool_v,
            jnp.asarray(np.zeros((rows, lane.length // e.block_size), np.int32)),
            jnp.asarray(np.zeros((rows, t, cfg.dim), np.float32)),
            zeros, jnp.asarray(np.ones(rows, np.int32)),
            jnp.asarray(np.zeros((rows, t), np.int32)), None, e._ssm, e._conv, zeros,
        )
        np.asarray(logits)

    def warm_decode(self, lane) -> None:
        import jax.numpy as jnp

        e = self.e
        zeros = jnp.asarray(np.zeros(lane.n_slots, np.int32))
        greedy, _logits, e._pool_k, e._pool_v, e._ssm, e._conv, e._expert_held, *_ = e._decode(
            e.params, e._pool_k, e._pool_v, jnp.asarray(np.zeros_like(lane.table)),
            zeros, zeros, zeros, e._ssm, e._conv, zeros, e._expert_held,
        )
        np.asarray(greedy)


def scope_maps(programs: dict) -> dict:
    """``caption_engine_sparse.scope_maps`` looking for this flavor's scopes
    (its pattern is a constant of its module, which this PR may not edit)."""
    with mock.patch.object(scoped, "SCOPES", SCOPES):
        return scoped.scope_maps(programs)


# -- correctness --------------------------------------------------------------
#
# WHAT A FLIPPED EXPERT DOES HERE, AND WHY THE REFERENCE FOLLOWS THE PROGRAM'S
# CHOICE. With seeded weights a token's last expert taken and first left out lie
# a hundredth of a score apart (64 sigmoid scores: the fourth and fifth largest
# are 0.13 of the logits' spread apart in the mean), the engine's bfloat16
# hidden state differs from the float32 reference's by a hundredth or two, and
# so the engine takes another expert than the reference in one layer in six at
# a token. The four weights are all but equal (top 4, renormalised), so one flip
# swaps a QUARTER of the layer's output. The cells that hold an eighth of a
# layer's experts compare logits at positions whose routing margin is wide; here
# all 64 experts are held, every layer's near-tie counts, and after eight sparse
# layers four prompts in five carry a flip somewhere (first-step logits read
# 0.06-0.09 where nothing flipped and 0.3-0.5 where something did; my chip runs,
# PR 54). That is rounding, not a fault, and no statistic of such logits tells
# it from a quarter of a layer computed wrong. So the programs HAND OUT what
# every token's router chose in every sparse layer (``MoEConfig.hand_out_choice``:
# their last output, which the engine reads nowhere), and the float32 reference
# FOLLOWS that choice: it takes the program's experts, weighs them by its own
# float32 scores of them, and everything else is its own. Then
#
# - every layer is held to bfloat16 rounding: first-step logits after prompts
#   inside one prefill chunk and over three chunks with padding in the last,
#   from the shared prefix's blocks AND tails snapshot, after each of 8 decode
#   steps through the pool and the tails; EVERY conv layer's tails after each
#   prompt and each decode step; for every request, not on a median;
# - the CHOICE is held apart: wherever the reference's own margin (on the
#   followed path) is at least ``routing_margin`` the program's experts are the
#   reference's own (but for ``routing_flip_share`` of them: a margin's noise
#   has a tail); under it a flip is rounding and is counted, not judged;
# - the router's arithmetic is held directly on inputs nothing has rounded
#   (``router_weight_tol``), and the FIRST conv layer's tails, below which
#   nothing is routed, to bfloat16 rounding (``tail_rms_tol``);
# - the engine's own XLA path (``paged_attention='gather'``) serves the prefix
#   requests from the kernel engine's first token and is held to the reference
#   that follows ITS choice by the same judges: the two engines round in
#   different places and flip different tokens, so each is compared with the
#   reference and not with the other.


@dataclasses.dataclass
class Group:
    """The prompts of one judge: prefixes of ONE seeded sequence (one more id
    than the longest, so that a decode step after it has a place), so the
    reference compiles one shape a group."""

    what: str
    spec: object  # the whole sequence as a request (its prefix_ids are the shared prefix or empty)
    ends: list[int]  # a prompt's last position in prefix + prompt ids

    @property
    def ids(self) -> list[int]:
        return list(self.spec.prefix_ids) + list(self.spec.prompt_ids)

    def requests(self):
        """[(request id, prompt ids, prefix ids)]: the prompts cut at ``ends``."""
        n_prefix = len(self.spec.prefix_ids)
        return [
            (f"{self.spec.request_id}-{k}", self.ids[n_prefix : end + 1], list(self.spec.prefix_ids))
            for k, end in enumerate(self.ends)
        ]


def plan(traffic, check) -> dict[str, Group]:
    """The three groups of prompts the engine's run serves: inside one prefill
    chunk, over three chunks with padding in the last, and from the shared
    prefix; ``check['prompts']`` lengths a group, spread over its range, the
    longest (a full chunk; one position short of three) always among them."""

    def group(spec, lengths, what):
        ends = sorted({int(round(e)) - 1 for e in np.linspace(lengths[0], lengths[1], int(check["prompts"]))})
        return Group(what, spec, ends)

    short, long = check["text_tokens"]
    shared = dataclasses.replace(
        traffic.request(10**6 + 100, prompt_len=int(check["prefix_prompt_tokens"][1]) + 1), request_id="check-prefix"
    )
    n_prefix = len(shared.prefix_ids)
    return {
        "short": group(_text_only(traffic, "check-text-short", short[1] + 1, 0), short,
                       f"{short[0]}-{short[1]}-token prompts (inside one prefill chunk)"),
        "long": group(_text_only(traffic, "check-text-long", long[1] + 1, 1), long,
                      f"{long[0]}-{long[1]}-token prompts (three prefill chunks, padding in the last)"),
        "prefix": group(shared, [n_prefix + n for n in check["prefix_prompt_tokens"]],
                        f"requests of the shared {n_prefix}-token prefix + {check['prefix_prompt_tokens']} prompt tokens"),
    }


@dataclasses.dataclass
class Followed:
    """One forward of the float32 reference over ``ids`` that FOLLOWS a choice."""

    h: object  # [T, dim]
    z: list  # every conv layer's z [T, dim]
    margins: np.ndarray  # [Ls, T]: the reference's own routing margin on the followed path
    own: np.ndarray  # [Ls, T, k]: its own choice there


def follow(ref, params, sizes, ids, choice=None, **low) -> Followed:
    """``choice`` [Ls, n, k] for the first ``n`` positions (the others, and all
    where None, take the reference's own: a causal model's earlier positions do
    not see them). ``low``: the reference's lower-precision knobs."""
    import jax.numpy as jnp

    z, margins, own = [], [], []
    taken = None
    if choice is not None:
        taken = np.full((choice.shape[0], len(ids), choice.shape[2]), -1, np.int32)
        taken[:, : choice.shape[1]] = choice
        taken = jnp.asarray(taken)
    h, _ = ref.forward(params, jnp.asarray(ids, jnp.int32), z=z, margins=margins, choices=own, follow=taken, **sizes, **low)
    first = sizes["moe"]["first_dense"]
    return Followed(h, z, np.stack([np.asarray(m) for m in margins[first:]]), np.stack([np.asarray(c) for c in own]))


def answers_at(ref, params, sizes, f: Followed, ends):
    """(logits [P, vocab], tails [P, Lc, 2 * dim]) at the positions ``ends``."""
    import jax.numpy as jnp

    logits = np.asarray(ref.logits_of(params, f.h[jnp.asarray(ends)], **sizes), np.float32)
    return logits, np.stack([np.asarray(ref.tails_after(f.z, end + 1)) for end in ends])


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def judge_logits(what: str, pairs, tol: float) -> bool:
    """EVERY pair of (got, want) logits: the largest difference over the
    reference's largest value against ``tol``."""
    errs = [_rel(g, w) for g, w in pairs]
    top = max(errs) if errs else float("nan")
    good = bool(np.isfinite(top) and top <= tol)
    log(f"correct: {what}: rel err {[round(e, 4) for e in errs]}, max {top:.4f} (tol {tol}) {'ok' if good else 'FAILED'}")
    return good


def judge_tails(what: str, got, want, check) -> bool:
    """``got`` / ``want``: [(tails ``[Lc, 2 * dim]``)] a request, for EVERY
    request: the FIRST conv layer's (nothing routed lies below it) to bfloat16
    rounding of one layer, and all the layers' at once (the reference followed
    the program's choice: what stands between the two is rounding, ten layers
    deep)."""
    pairs = list(zip(got, want))
    ok = _judge_rms(f"{what}, the first conv layer's tails vs float32 reference", [(g[0], w[0]) for g, w in pairs],
                    check["tail_rms_tol"], of=np.max)
    return ok & _judge_rms(f"{what}, every conv layer's tails vs float32 reference", pairs, check["tails_rms_tol"], of=np.max)


def judge_choice(what: str, triples, check) -> bool:
    """``triples``: [(the program's choice ``[Ls, n, k]``, the reference's own
    there, its margins ``[Ls, n]``)]. Where the margin is at least
    ``routing_margin`` the program's experts must be the reference's, but for
    ``routing_flip_share`` of those (token, layer)s: the noise of a margin has a
    tail, and one flip in fifty thousand is no fault where a lost bias flips
    one in a hundred; under the margin another expert is rounding: counted,
    and the widest margin at which it happened is read out."""
    flipped = wide = total = at_wide = 0
    widest, by_layer = 0.0, 0
    for got, own, margin in triples:
        other = ~(np.sort(got, axis=-1) == np.sort(own, axis=-1)).all(axis=-1)  # [Ls, n]
        wide_here = margin >= check["routing_margin"]
        flipped, total, wide = flipped + int(other.sum()), total + other.size, wide + int(wide_here.sum())
        at_wide += int((other & wide_here).sum())
        widest = max(widest, float(margin[other].max()) if other.any() else 0.0)
        by_layer = by_layer + other.sum(axis=1)
    share = at_wide / wide if wide else float("nan")
    good = total > 0 and wide > 0 and share <= check["routing_flip_share"]
    log(
        f"correct: {what}: the program's experts vs the reference's own on the followed path: {flipped} of {total} "
        f"(token, layer)s took another expert (by layer {np.asarray(by_layer).tolist()}), the widest margin among them "
        f"{widest:.4f}; of the {wide} with a margin of {check['routing_margin']} or more, {at_wide} "
        f"(a share of {share:.5f}, tol {check['routing_flip_share']}) {'ok' if good else 'FAILED'}"
    )
    return bool(good)


def check_router(ref, cfg, params, sizes, traffic, check, low=None) -> bool:
    """The float32 router of the configuration's file on inputs that nothing
    has rounded: the first sparse layer's, on the reference's own float32 hidden
    states of one seeded prompt. ``low`` (the lower-precision readings): the
    reference's router in fewer bits stands in the program's place."""
    import functools

    import jax
    import jax.numpy as jnp

    spec = _text_only(traffic, "check-router", int(check["router_tokens"]), 900)
    n, *want, margin = ref.first_router(params, jnp.asarray(spec.prompt_ids, jnp.int32), **sizes)
    moe_params = params["params"][f"layer_{cfg.moe.first_dense}"]["moe"]
    if low is None:
        got, who = program_router(cfg, moe_params, n), "the program's router"
    else:
        with jax.default_matmul_precision("highest"):
            got = jax.jit(functools.partial(
                ref.route, moe=sizes["moe"], router_mantissa_bits=low.get("router_mantissa_bits", 23)
            ))(n, moe_params)[:2]
        who = "the reference's router in the control's bits"
    return _judge_router(
        f"{who} vs the float32 reference's on the same float32 hidden states ({len(spec.prompt_ids)}-token prompt, "
        "the first sparse layer)", got, want, margin, check,
    )


def decode_specs(traffic, check):
    return [
        _text_only(traffic, f"check-decode-{j}", int(check["decode_prompt_tokens"]), 500 + j)
        for j in range(int(check["decode_requests"]))
    ]


def check_served(what, ref, params, sizes, private, group: Group, served, check, steps: int = 0) -> bool:
    """The requests ``served`` (indices into ``group.requests()``) of one engine
    (``private``: its spies) against the reference that follows each one's own
    choice: first-step logits, the tails after the prompt, the choice itself
    and, with ``steps`` = 1, the logits after the first decode step (from the
    token the request made first)."""
    requests, n_prefix = group.requests(), len(group.spec.prefix_ids)
    first, after, got_tails, want_tails, choices = [], [], [], [], []
    for k in served:
        name, end = requests[k][0], group.ends[k]
        # (a seeded model's first greedy token is the end-of-sequence id once in some thousands: no decode step then)
        step = steps if name in private.tokens and private.decode_logits.get(name) else 0
        if step != steps:
            log(f"correct: {what}: {name} ended on its first token: its first step alone is read")
        choice = private.choice_of(name, n_prefix, end + 1, step)
        if choice is None:
            log(f"correct: {what}: {name}'s programs handed out no choice for some position: FAILED")
            return False
        ids = group.ids[: end + 1] + ([private.tokens[name][0]] if step else []) + group.ids[end + 1 + step :]
        f = follow(ref, params, sizes, ids, choice)
        logits, tails = answers_at(ref, params, sizes, f, [end, end + step])
        first.append((private.first_logits[name], logits[0]))
        if step:
            after.append((private.decode_logits[name][0], logits[1]))
        got_tails.append(private.tails[name])
        want_tails.append(tails[0])
        n = choice.shape[1]
        choices.append((choice, f.own[:, :n], f.margins[:, :n]))
    ok = judge_logits(f"{what}, first-step logits vs the float32 reference that follows the program's choice", first,
                      check["reference_rel_tol"])
    if steps and (after or not first):
        ok &= judge_logits(f"{what}, logits of the first decode step (from the request's own first token)", after,
                           check["decode_rel_tol"])
    ok &= judge_tails(what, got_tails, want_tails, check)
    return ok & judge_choice(what, choices, check)


def check_against_reference(engine, private, traffic, cfg, check, params=None):
    """The engine's timed path against the plain float32 forward pass that
    follows its choice of experts, on ``params`` (the engine's own tree unless a
    fault was planted in the engine's). Returns (ok, the prefix group): the
    XLA-path check serves it again."""
    ref = load_module("reference", REFERENCE)
    sizes = ref.model_kwargs(cfg)
    params = engine.params if params is None else params
    groups = plan(traffic, check)
    private.keep_choice = True
    ok = True
    for key, group in groups.items():
        requests = group.requests()
        steps = 0
        if key == "prefix":  # through the prefix cache: the build, then requests that are hits; two tokens each
            snapshots0 = engine.stats()["prefix_state_snapshots"]
            if not _serve(engine, traffic, "check-prefix-build", requests[0][1], requests[0][2]):
                return False, None
            steps = 1
        served = [
            k for k, (name, prompt, prefix) in enumerate(requests)
            if _serve(engine, traffic, name, prompt, prefix, max_new=1 + steps)
        ]
        ok &= len(served) == len(requests)
        ok &= check_served(group.what, ref, params, sizes, private, group, served, check, steps)
        if key == "prefix" and engine.stats()["prefix_state_snapshots"] - snapshots0 < len(requests):
            log("correct: a prefix request did not start from a tails snapshot: FAILED")
            ok = False

    # decode through the pool and the tails: the tokens are the engine's own
    steps, logits, got_tails, want_tails, choices = int(check["decode_steps"]), [], [], [], []
    requests = decode_specs(traffic, check)
    for j, spec in enumerate(requests):
        for again in range(3):  # a request that met the end-of-sequence id before its steps were made: another prompt
            name, t = spec.request_id, len(spec.prompt_ids)
            if not _serve(engine, traffic, name, spec.prompt_ids, max_new=steps + 1):
                return False, groups["prefix"]
            if name in private.tokens:
                break
            log(f"correct: {name} ended early on the end-of-sequence id: another seeded prompt in its place")
            spec = _text_only(traffic, f"check-decode-{j}-{again + 1}", t, 500 + j + 100 * (again + 1))
        generated, seen = private.tokens.get(name, []), private.decode_logits.get(name, [])
        choice = private.choice_of(name, 0, t, steps)
        if len(generated) != steps + 1 or len(seen) != steps or len(private.step_tails.get(name, [])) != steps or choice is None:
            log(f"correct: {name} made {len(generated)} tokens in {len(seen)} steps, choice {choice is not None}: FAILED")
            return False, groups["prefix"]
        f = follow(ref, params, sizes, list(spec.prompt_ids) + generated[:steps], choice)
        want, tails = answers_at(ref, params, sizes, f, list(range(t, t + steps)))
        logits += list(zip(seen, want))
        got_tails += private.step_tails[name]
        want_tails += list(tails)
        choices.append((choice[:, t:], f.own[:, t:], f.margins[:, t:]))
    what = f"the {steps} decode steps of {len(requests)} requests"
    ok &= judge_logits(
        f"logits after {what} ({len(logits)}) vs the reference's ONE full forward over prompt + generated ids, "
        "following the program's choice", logits, check["decode_rel_tol"],
    )
    ok &= judge_tails(f"after each of {what}", got_tails, want_tails, check)
    ok &= judge_choice(f"{what}, the steps' own positions", choices, check)
    ok &= check_router(ref, cfg, params, sizes, traffic, check)
    private.keep_choice = False
    return bool(ok), groups["prefix"]


def check_xla_path(engine, private, traffic, cfg, check, group: Group, params=None) -> bool:
    """The prefix group once more on the engine's own XLA path
    (``paged_attention='gather'``: attention over gathered views, the same sorted
    dispatch through ``ragged_dot``; one slot), same parameters. Its first TOKEN
    is the kernel engine's (``hand_first_logits``; its first logits stay its
    own), so both decode the same ids. Two bfloat16 computations that round in
    different places flip different experts, so the XLA engine is held to the
    reference that follows ITS choice, by the judges the kernel engine was held
    by (``xla_path_rel_tol`` for both its logits)."""
    from cosmos_curate_tpu.models.vlm import CaptionEngine

    os.environ.update(CURATE_FLASH_DECODE="0", CURATE_FLASH_PREFILL="0")
    other = CaptionEngine(
        cfg, kv_lanes=((engine.lanes[0].length, 1),), params=engine.params,
        paged_attention="gather", prefill_chunk=engine.prefill_chunk, block_size=engine.block_size,
    )
    other.setup()
    ref = load_module("reference", REFERENCE)
    requests = group.requests()
    try:
        for name, _prompt, _prefix in requests:  # BEFORE the spies, which keep the engine's own row
            hand_first_logits(other, name, private.first_logits[name])
        other_private = _ConvPrivate(other)
        other_private.keep_choice = True
        for name, prompt, prefix in requests:
            if not _serve(other, traffic, name, prompt, prefix, max_new=2, hold=False):
                return False
            mine, theirs = private.tokens.get(name, [None])[0], other_private.tokens.get(name, [None])[0]
            if mine != theirs:  # (both None where that token is the end-of-sequence id)
                log(f"correct: the XLA engine decoded {name} from token {theirs}, not {mine}: FAILED")
                return False
        limits = dict(check, reference_rel_tol=check["xla_path_rel_tol"], decode_rel_tol=check["xla_path_rel_tol"])
        return check_served(
            "the prefix requests on the engine's XLA path", ref, engine.params if params is None else params,
            ref.model_kwargs(cfg), other_private, group, list(range(len(requests))), limits, steps=1,
        )
    finally:
        other.shutdown()


# -- the run ------------------------------------------------------------------


def run(cell: Cell, *, seed: int, seconds: float, trace: bool, rehearse: bool, devices, clock) -> dict:
    import jax

    from cosmos_curate_tpu.models.registry import WEIGHTS_DIR_ENV
    from cosmos_curate_tpu.models.vlm import CaptionEngine
    from cosmos_curate_tpu.utils.jax_cache import enable_persistent_cache

    conf = cell.config
    # the program looks for staged weights and tokenizers under /tmp unless told
    # where: nothing is staged here, and nothing outside the checkout is read
    os.environ[WEIGHTS_DIR_ENV] = str(measure.CACHE_DIR / "weights" / "none")
    log(f"compile cache at {enable_persistent_cache()}")
    cfg, lanes, chunk, prefill_rows = _program_config(cell, rehearse)
    compiles = measure.CompileCounter()

    with clock.part("params"):
        params = make_params(cfg, seed)
        jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"{n_params / 1e9:.3f} B parameters made from seed {seed}, in the serving types")

    with clock.part("engine"):
        engine = CaptionEngine(
            cfg, kv_lanes=lanes, async_prep=bool(conf["serving"]["async_prep"]),
            paged_attention=conf["serving"]["paged_attention"],
            block_size=int(conf["serving"]["block_size"]), prefill_chunk=chunk, params=params,
            max_prefill_rows=prefill_rows,
        )
        engine.setup(seed)
        private = _ConvPrivate(engine)
    traffic_mod = load_module("traffic", cell.traffic["generator"])
    tparams = cell.traffic_params(rehearse)
    if int(tparams["frames"]):
        raise ValueError(f"{cell.name}: the flavor is text only and the mix sends frames")
    traffic = traffic_mod.CaptionTraffic(tparams, seed, vocab=cfg.vocab, image_size=cfg.vision.image_size)
    use_lanes, lengths = reachable(engine, traffic, engine.prefill_chunk)
    loop = scoped.DigestLoop(engine, private, traffic, sum(l.n_slots for l in use_lanes), int(tparams["backlog"]))
    stats = engine.stats()
    log(
        f"lanes {[(l.length, l.n_slots) for l in engine.lanes]}; the mix reaches "
        f"{[(l.length, l.n_slots) for l in use_lanes]}, prefill lengths {lengths}, "
        f"prompt grid {traffic.grid[0]}..{traffic.grid[-1]} step {tparams['prompt_tokens']['step']}; "
        f"resident: parameters {stats['param_bytes_per_chip'] / 2**30:.2f} GiB, convolution tails "
        f"{stats['conv_tail_bytes_per_chip'] / 2**20:.1f} MiB ({stats['recurrent_rows_total']} rows), "
        f"KV pool {stats['kv_pool_bytes_per_chip'] / 2**30:.2f} GiB"
    )

    with clock.part("warm_programs"):
        if trace and not rehearse:  # a traced run's own: the end-to-end runs pay nothing for it
            private.programs = {}
        for lane in use_lanes:
            rows = 1
            # prompts in prefill at once: as many as a program takes (the
            # flavor's prefill_rows) or the lane has slots; every such program
            # is warmed, so a burst after a stall compiles nothing in the window
            while rows <= min(int(tparams["warm_rows"]), lane.n_slots, prefill_rows or lane.n_slots):
                for t in lengths:
                    t0 = time.monotonic()
                    private.warm_prefill(lane, rows, t)
                    log(f"warm: prefill lane {lane.length} rows {rows} T {t}: {time.monotonic() - t0:.2f} s")
                rows *= 2
            t0 = time.monotonic()
            private.warm_decode(lane)
            log(f"warm: decode lane {lane.length} rows {lane.n_slots}: {time.monotonic() - t0:.2f} s")
        maps = None
        if private.programs is not None:
            t0 = time.monotonic()
            maps = scope_maps(private.programs)
            log(f"scopes: the compiled text of the warmed programs read in {time.monotonic() - t0:.2f} s")
        private.programs = None

    check = dict(conf["check"], **(conf["rehearse"].get("check", {}) if rehearse else {}))
    with clock.part("correct"):
        correct, prefix_group = check_against_reference(engine, private, traffic, cfg, check)
        correct &= prefix_group is not None and check_xla_path(engine, private, traffic, cfg, check, prefix_group)
        engine.run_until_complete()  # the last hold request ends
        private.place.clear()  # nothing of the loop is a check request

    with clock.part("ramp"):
        loop.ramp(timeout_s=240.0)
    setup_s = clock.close()

    # ---- the measured window (drivers/caption_engine.py's, line for line) ----
    tracer = measure.Tracer(cell.name) if trace else None
    trace_from = 0.25 * seconds
    trace_for = float(tparams["trace_seconds"])
    stats0, phases0 = engine.stats(), engine.phase_seconds
    done0, lost_base = len(loop.results), loop.submitted - len(loop.results) - private.in_engine()
    slice_span = None
    with compiles.window():
        t_start = time.monotonic()
        tokens0 = loop.tokens_emitted()
        marks: list[tuple[float, int]] = []  # (seconds into the window, tokens so far), every 5 s
        longest = (0.0, 0.0)  # the longest turn of the loop and when it began: a stall shows here
        while (now := time.monotonic()) < t_start + seconds:
            if now - t_start >= 5.0 * (len(marks) + 1):
                marks.append((round(now - t_start, 3), loop.tokens_emitted() - tokens0))
            if tracer is not None:
                if tracer.started_at is None and now >= t_start + trace_from:
                    tracer.start()
                    slice_span = annotate(trace_reduce.SLICE_SPAN)
                    slice_span.__enter__()
                    loop.decode_lengths, private.prefill_valid = [], []
                elif tracer.active and now >= tracer.started_at + trace_for:
                    slice_span.__exit__(None, None, None)
                    tracer.stop()
                    decode_lengths, loop.decode_lengths = loop.decode_lengths, None
                    prefill_valid, private.prefill_valid = private.prefill_valid, None
            loop.turn()
            if (took := time.monotonic() - now) > longest[0]:
                longest = (took, now - t_start)
        tokens1 = loop.tokens_emitted()
        t_end = time.monotonic()
    if tracer is not None and tracer.active:
        raise RuntimeError("the window closed before the traced slice did: --seconds is too short")
    window_s = t_end - t_start
    stats1, phases1 = engine.stats(), engine.phase_seconds  # reads the device's count: after the window
    finished = len(loop.results) - done0
    lost = loop.submitted - len(loop.results) - private.in_engine() - lost_base
    tokens = tokens1 - tokens0
    counted = stats1["decode_tokens"] - stats0["decode_tokens"]
    log(
        f"window {window_s:.3f} s: {tokens} output tokens ({counted} of them decode steps' by "
        f"the engine's counter), {finished} requests finished, {lost} lost, "
        f"{loop.early_eos} ended early on EOS since start; "
        f"prompt tokens prefilled {stats1['prefill_tokens'] - stats0['prefill_tokens']}"
    )
    log(f"tokens by time into the window: {marks}; longest turn {longest[0]:.3f} s at {longest[1]:.2f} s")
    log(f"engine stats at window end (since the engine started): {stats1}")
    log(f"decode programs in window: {stats1['paged_kernel_steps'] - stats0['paged_kernel_steps']}")
    log(f"engine phase seconds in window: { {k: round(phases1[k] - phases0[k], 3) for k in phases1} }")

    record = {
        "correct": bool(correct),
        "attempted": finished + lost,
        "failed": lost,
        "setup_s": setup_s,
        "window_s": window_s,
        "end_to_end": {"output_tok_per_s": tokens / window_s, "setup_s": setup_s},
        "stats_delta": {k: stats1[k] - stats0[k] for k in ("decode_tokens", "decode_s", "prefill_tokens", "prefill_s", "paged_kernel_steps")},
        "phase_delta": {k: phases1[k] - phases0[k] for k in phases1},
        "compiles_in_window": compiles.count,
        "devices": devices,
        "rehearse": rehearse,
        "trace": None,
        "expert_trace": None,
        "program_s": None,
        "scope_s": None,
        # the tails and the experts, as the engine counts them
        "conv": {
            k: stats1[k] for k in ("conv_tail_bytes_per_chip", "recurrent_rows_total", "recurrent_rows_used_peak")
        } | {
            k: stats1[k] - stats0[k] for k in (
                "prefix_state_snapshots", "expert_assignments_held", "expert_assignments_held_live",
            )
        },
    }
    if tracer is not None:
        planes = trace_reduce.load_xplane(tracer.xplane())
        measure.keep_trace_for_reading(planes, cell.name + (".rehearsal" if rehearse else ""), HOST_SPANS)
        try:
            summary = trace_reduce.reduce(planes, kernels=KERNELS, host_spans=HOST_SPANS, chips=len(devices))
        except LookupError as e:
            # a slice in which no prompt was prefilled: the decode kernel alone
            log(f"WARNING: {e}; reduced with the decode kernel alone")
            summary = trace_reduce.reduce(
                planes, kernels={"paged_decode": KERNELS["paged_decode"]}, host_spans=HOST_SPANS,
                chips=len(devices),
            )
        experts = trace_reduce.reduce(planes, kernels=EXPERT_KERNELS, chips=len(devices))
        scopes = scoped.scope_seconds(planes, maps) if maps else None
        programs = program_seconds(planes)
        tracer.discard()
        record["trace"] = summary
        m = cfg.moe
        record["slice"] = {
            "decode_lengths": decode_lengths,
            "prefill_valid": prefill_valid,
            # the pool's L: the ATTENTION layers alone hold K/V
            "kv_shape": dict(
                n_layers=len(cfg.kv_layers), n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                block_size=engine.block_size, dtype_bytes=2,
            ),
            "attention_shape": dict(n_layers=len(cfg.kv_layers), n_heads=cfg.n_heads, head_dim=cfg.head_dim),
            "conv_shape": dict(n_layers=len(cfg.ssm_layers), dim=cfg.dim, taps=cfg.short_conv.l_cache, dtype_bytes=2),
            "expert_shape": dict(
                dim=cfg.dim, width=m.hidden, held=m.held_experts[1], dtype_bytes=2,
                sparse_layers=cfg.n_layers - m.first_dense, router_outputs=m.n_experts, top_k=m.top_k,
            ),
        }
        if summary is not None:
            record["expert_trace"] = {"kernel_s": experts.kernel_s, "kernel_calls": experts.kernel_calls}
            record["scope_s"] = scopes
            record["program_s"] = programs
            log(
                f"traced slice {summary.window_s:.3f} s, {summary.events} device events: busy "
                f"{summary.busy_s:.3f} s, paged kernels {summary.kernel_s} calls {summary.kernel_calls}, "
                f"grouped matmul {experts.kernel_s} calls {experts.kernel_calls}, programs by kind {programs}, "
                f"device seconds by scope { {f'{k}:{s}': round(v, 4) for (k, s), v in sorted((scopes or {}).items())} }, "
                f"{len(decode_lengths)} decode and {len(prefill_valid)} prefill programs in the slice, gaps {summary.gap_s}"
            )
        # the cell's own readers are files no `BENCHMARK.json` entry names yet (PERF.md section 7): the harness
        # does not read them, so the traced run puts them on a line of its own
        seen = dict(record, device=measure.device_block(devices))
        readers = {name: load_module("layer_metrics", name) for name in OWN_READERS}
        own = {  # (a share or a time from the CPU is never written under a device metric's name)
            name: reader.read(seen) if not rehearse or reader.SOURCE == "program_counter" else None
            for name, reader in readers.items()
        }
        log(f"the cell's own readers: {json.dumps(own)}")
    return record


# -- the second reading of check's limits --------------------------------------

# the reference's own knobs; `stated` is the precision the file states (the
# engine's bfloat16 activations and tails over a float32 router): it must pass
CONTROLS = {
    "router": ("a bfloat16 router (its outputs and its scores rounded to bfloat16)", dict(router_mantissa_bits=7)),
    "activations": ("8-bit-float activations (3 bits of mantissa)", dict(activation_mantissa_bits=3)),
    "tails": ("8-bit-float tails (3 bits of mantissa) under bfloat16 activations", dict(activation_mantissa_bits=7, tail_mantissa_bits=3)),
    "stated": ("bfloat16 activations and tails (what the engine computes in and stores)", dict(activation_mantissa_bits=7, tail_mantissa_bits=7)),
}


def _cell_pieces(seed: int, rehearse: bool):
    """(cell, cfg, lanes, chunk, prefill rows, check, reference, seeded parameters, traffic)."""
    from perfbench.catalog import load_cell

    cell = load_cell("lfm2-24b-a2b-pp5.text-rewrite")
    conf = cell.config
    cfg, lanes, chunk, prefill_rows = _program_config(cell, rehearse)
    check = dict(conf["check"], **(conf["rehearse"].get("check", {}) if rehearse else {}))
    params = make_params(cfg, seed)
    tparams = cell.traffic_params(rehearse)
    traffic = load_module("traffic", cell.traffic["generator"]).CaptionTraffic(
        tparams, seed, vocab=cfg.vocab, image_size=cfg.vision.image_size
    )
    return cell, cfg, lanes, chunk, prefill_rows, check, load_module("reference", REFERENCE), params, traffic


def lower_precision(seed: int, names, rehearse: bool = False) -> dict[str, bool]:
    """``check``'s judges with the reference itself, computing in fewer bits, in
    the PROGRAM'S place (its logits, its tails AND its choice of experts, which
    the float32 reference then follows as it follows the program's), on seeded
    parameters at the configuration's full size (layer by layer on the device):
    the second of the two readings each limit lies between. {control: whether
    it came out ``correct``}."""
    import jax

    _cell, cfg, _lanes, _chunk, _rows, check, ref, params, traffic = _cell_pieces(seed, rehearse)
    sizes = ref.model_kwargs(cfg)
    groups = plan(traffic, check)
    steps = int(check["decode_steps"])
    # a prompt and `steps` seeded tokens more: the positions after the prompt
    decode = [
        (list(spec.prompt_ids) + list(_text_only(traffic, "more", steps, 700).prompt_ids), len(spec.prompt_ids))
        for spec in decode_specs(traffic, check)
    ]

    def both(ids, low):
        """(the control over ``ids``, the float32 reference following the control's choice)."""
        got = follow(ref, params, sizes, ids, **low)
        return got, follow(ref, params, sizes, ids, got.own)

    verdicts = {}
    for name in names:
        what, low = CONTROLS[name]
        log(f"control: the reference with {what} in the program's place")
        ok = True
        for key, group in groups.items():
            got, want = both(group.ids, low)
            (logits, tails), (logits0, tails0) = (answers_at(ref, params, sizes, f, group.ends) for f in (got, want))
            ok &= judge_logits(f"{group.what}, first-step logits vs the float32 reference that follows the control's choice",
                               list(zip(logits, logits0)), check["reference_rel_tol"])
            ok &= judge_tails(group.what, list(tails), list(tails0), check)
            ok &= judge_choice(group.what, [(got.own, want.own, want.margins)], check)
            if key == "prefix":  # the XLA path's place: the same judges under its own limit, and the position after
                after = [e + 1 for e in group.ends]
                pairs = list(zip(answers_at(ref, params, sizes, got, after)[0], answers_at(ref, params, sizes, want, after)[0]))
                ok &= judge_logits("the prefix requests, first-step logits (the XLA path's limit)", list(zip(logits, logits0)),
                                   check["xla_path_rel_tol"])
                ok &= judge_logits("the same, logits of the position after", pairs, check["xla_path_rel_tol"])
        logits, got_tails, want_tails, choices = [], [], [], []
        for ids, t in decode:
            got, want = both(ids, low)
            at = list(range(t, t + steps))
            (lg, tg), (lw, tw) = (answers_at(ref, params, sizes, f, at) for f in (got, want))
            logits += list(zip(lg, lw))
            got_tails += list(tg)
            want_tails += list(tw)
            choices.append((got.own[:, t:], want.own[:, t:], want.margins[:, t:]))
        ok &= judge_logits(
            f"logits at the {steps} positions after the prompt of {len(decode)} sequences ({len(logits)}) vs the float32 "
            "reference that follows the control's choice", logits, check["decode_rel_tol"],
        )
        ok &= judge_tails(f"after each of those {len(got_tails)} positions", got_tails, want_tails, check)
        ok &= judge_choice("those positions", choices, check)
        ok &= check_router(ref, cfg, params, sizes, traffic, check, low=low)
        verdicts[name] = bool(ok)
        log(f"control: the reference with {what}: correct {bool(ok)}")
    jax.effects_barrier()
    return verdicts


# -- a fault planted in the engine's parameters ---------------------------------


def _swap(tree, a, b):
    """The tree with the leaves (or subtrees) at paths ``a`` and ``b`` exchanged: no array is copied."""
    def put(node, path, value):
        return {**node, path[0]: value if len(path) == 1 else put(node[path[0]], path[1:], value)}

    def get(node, path):
        return node if not path else get(node[path[0]], path[1:])

    return put(put(tree, a, get(tree, b)), b, get(tree, a))


def faults(cfg) -> dict:
    """{name: (what, params -> the faulty tree)}: faults in the layers ABOVE the
    first expert layer and in the head, where nothing but the reference that
    follows the program's choice can see them. Each is a re-wiring of the tree
    or one small array: nothing the size of a table is made."""
    import jax.numpy as jnp

    last = cfg.n_layers - 1
    second_attention = [i for i, kind in enumerate(cfg.layer_types) if kind == "full_attention"][-1]
    first_attention = cfg.layer_types.index("full_attention")
    sparse = [i for i in range(cfg.moe.first_dense, cfg.n_layers)]

    def one_table(params):  # ONE expert of 64 in the last layer takes its neighbour's down-projection
        down = params["params"][f"layer_{last}"]["moe"]["down"]
        moe = {**params["params"][f"layer_{last}"]["moe"], "down": down.at[0].set(down[1])}
        return {"params": {**params["params"], f"layer_{last}": {**params["params"][f"layer_{last}"], "moe": moe}}}

    def no_bias(params):  # the last layer's selection bias lost: the choice moves, the weights do not
        moe = params["params"][f"layer_{last}"]["moe"]
        moe = {**moe, "router_bias": jnp.zeros_like(moe["router_bias"])}
        return {"params": {**params["params"], f"layer_{last}": {**params["params"][f"layer_{last}"], "moe": moe}}}

    def final_norm(params):  # the final norm's scale a tenth off (5% reads twice the rounding and passes: PERF.md)
        scale = params["params"]["ln_f"]["scale"]
        return {"params": {**params["params"], "ln_f": {"scale": scale * 1.1}}}

    return {
        "tables-swapped": (
            f"layers {sparse[-2]} and {last} hold each other's expert tables",
            lambda p: {"params": _swap(_swap(p["params"], (f"layer_{sparse[-2]}", "moe", "gate_up"), (f"layer_{last}", "moe", "gate_up")),
                                       (f"layer_{sparse[-2]}", "moe", "down"), (f"layer_{last}", "moe", "down"))},
        ),
        "one-table": (f"expert 0 of layer {last} multiplies by expert 1's down-projection (one table of 64 in one layer of 10)", one_table),
        "kv-projection": (
            f"the second attention layer ({second_attention}) projects its keys with the first one's ({first_attention}) weights",
            lambda p: {"params": _swap(p["params"], (f"layer_{second_attention}", "k"), (f"layer_{first_attention}", "k"))
                       | {f"layer_{first_attention}": p["params"][f"layer_{first_attention}"]}},
        ),
        "bias-lost": (f"layer {last}'s selection bias is zero", no_bias),
        "final-norm": ("the final norm's scale is a tenth off", final_norm),
    }


def planted(seed: int, names, rehearse: bool = False) -> dict[str, bool]:
    """``check_against_reference`` on an engine whose parameters carry a planted
    fault while the reference's do not, at the configuration's full size: the
    second reading of what the judges tell from a program that is WRONG (not
    merely coarser) in the deep layers. {fault: whether it came out ``correct``}:
    every one must come out False."""
    from cosmos_curate_tpu.models.registry import WEIGHTS_DIR_ENV
    from cosmos_curate_tpu.models.vlm import CaptionEngine
    from cosmos_curate_tpu.utils.jax_cache import enable_persistent_cache

    os.environ[WEIGHTS_DIR_ENV] = str(measure.CACHE_DIR / "weights" / "none")  # (as `run`: nothing outside the checkout)
    log(f"compile cache at {enable_persistent_cache()}")
    cell, cfg, lanes, chunk, prefill_rows, check, _ref, params, traffic = _cell_pieces(seed, rehearse)
    serving = cell.config["serving"]
    verdicts = {}
    for name in names:
        what, plant = faults(cfg)[name]
        log(f"fault: {what}")
        engine = CaptionEngine(
            cfg, kv_lanes=lanes, async_prep=bool(serving["async_prep"]), paged_attention=serving["paged_attention"],
            block_size=int(serving["block_size"]), prefill_chunk=chunk, params=plant(params), max_prefill_rows=prefill_rows,
        )
        engine.setup(seed)
        try:
            ok, _ = check_against_reference(engine, _ConvPrivate(engine), traffic, cfg, check, params=params)
            engine.run_until_complete()
        finally:
            engine.shutdown()
        verdicts[name] = bool(ok)
        log(f"fault: {what}: correct {bool(ok)}")
    return verdicts


if __name__ == "__main__":
    import argparse
    import sys

    p = argparse.ArgumentParser(description="the second readings of check's limits: " + lower_precision.__doc__.split("\n\n")[0])
    p.add_argument("--lower-precision", nargs="*", choices=list(CONTROLS))
    p.add_argument("--plant", nargs="*", help="faults planted in the engine's parameters (none named: all); exit 0 where every one is caught")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse", action="store_true", help="the tiny preset on the CPU: the control flow, no reading")
    args = p.parse_args()
    if (args.lower_precision is None) == (args.plant is None):
        p.error("one of --lower-precision and --plant")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.plant is not None:
        sys.exit(1 if any(planted(args.seed, args.plant or ["tables-swapped", "one-table", "kv-projection", "bias-lost", "final-norm"], args.rehearse).values()) else 0)
    sys.exit(0 if all(lower_precision(args.seed, args.lower_precision or list(CONTROLS), args.rehearse).values()) else 1)
