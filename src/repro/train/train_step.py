"""Train-step factory: loss, grads, clipping, AdamW, microbatch
accumulation, and the majority-vote compressed-DP variant.

Two step flavors:

* ``make_train_step``     — plain pjit step: XLA inserts the gradient
  all-reduce implied by the shardings (baseline).
* ``make_compressed_train_step`` — shard_map over the data axes with
  *explicit* majority-vote sign compression of gradients (the paper's MAJ
  primitive lifted to distributed optimization): per-replica gradient signs
  are bit-packed 32×, all-gathered, and the element-wise majority vote —
  computed exactly like a SIMDRAM TRA, as a bit-plane popcount majority —
  becomes the update direction, with local error feedback.  Wire bytes drop
  32× vs an f32 ring all-reduce.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..models.transformer import forward
from .optimizer import AdamWConfig, AdamWState, adamw_init, adamw_update


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt: AdamWState
    error_fb: Any = None          # error-feedback residual (compressed DP)


def init_train_state(params, compressed: bool = False) -> TrainState:
    err = jax.tree.map(jnp.zeros_like, params) if compressed else None
    return TrainState(params=params, opt=adamw_init(params), error_fb=err)


def softmax_xent(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    ll = jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return -jnp.mean(ll)


def make_loss_fn(cfg: ModelConfig, aux_weight: float = 0.01,
                 loss_chunk: int = 0):
    """``loss_chunk > 0`` enables the chunked-vocab loss: the LM head and
    cross-entropy run per sequence-chunk inside a scan, so the (tokens ×
    vocab) f32 logits tensor is never materialized — the memory-term
    optimization for large-vocab training cells (§Perf hillclimb)."""
    def loss_fn(params, batch):
        if not loss_chunk:
            logits, aux, _ = forward(params, cfg, batch)
            loss = softmax_xent(logits, batch["labels"])
            return loss + aux_weight * aux, {"loss": loss, "aux": aux}
        hidden, aux, _ = forward(params, cfg, batch, return_hidden=True)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        b, s, d = hidden.shape
        n_chunks = max(1, s // loss_chunk)
        hs = hidden.reshape(b, n_chunks, s // n_chunks, d).transpose(
            1, 0, 2, 3)
        ls = batch["labels"].reshape(b, n_chunks, s // n_chunks).transpose(
            1, 0, 2)

        def chunk_loss(carry, xs):
            h, lab = xs
            logits = jnp.einsum("bsd,dv->bsv", h, head.astype(h.dtype))
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            ll = jnp.take_along_axis(logp, lab[..., None], -1)[..., 0]
            return carry - jnp.sum(ll), None

        total, _ = jax.lax.scan(chunk_loss, jnp.zeros(()), (hs, ls))
        loss = total / (b * s)
        return loss + aux_weight * aux, {"loss": loss, "aux": aux}
    return loss_fn


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                    microbatches: int = 1, loss_chunk: int = 0):
    """Plain (pjit) step with optional microbatch gradient accumulation —
    accumulation is expressed as a scan so XLA can overlap the k-th
    microbatch's gradient reduction with the (k+1)-th backward pass."""
    loss_fn = make_loss_fn(cfg, loss_chunk=loss_chunk)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state: TrainState, batch):
        if microbatches == 1:
            (_, metrics), grads = grad_fn(state.params, batch)
        else:
            def split(x):
                b = x.shape[0]
                return x.reshape(microbatches, b // microbatches, *x.shape[1:])
            mb = jax.tree.map(split, batch)

            def acc_body(carry, mbatch):
                g_acc, _ = carry
                (_, metrics), g = grad_fn(state.params, mbatch)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                return (g_acc, metrics), None

            zero = jax.tree.map(jnp.zeros_like, state.params)
            (grads, metrics), _ = jax.lax.scan(
                acc_body, (zero, {"loss": jnp.zeros(()),
                                  "aux": jnp.zeros(())}), mb)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
        params, opt, stats = adamw_update(opt_cfg, state.opt, state.params,
                                          grads)
        metrics.update(stats)
        return TrainState(params, opt, state.error_fb), metrics

    return train_step


# ---------------------------------------------------------------------------
# Majority-vote compressed data parallelism (paper technique, lifted)
# ---------------------------------------------------------------------------

def _pack_signs(g: jax.Array) -> jax.Array:
    """f32 (..., n) → uint32 (..., n/32) packed sign bits (1 ⇔ g ≥ 0)."""
    flat = g.reshape(-1)
    pad = (-flat.shape[0]) % 32
    flat = jnp.pad(flat, (0, pad))
    bits = (flat >= 0).astype(jnp.uint32).reshape(-1, 32)
    return (bits << jnp.arange(32, dtype=jnp.uint32)).sum(-1,
                                                          dtype=jnp.uint32)


def _majority_from_packed(words: jax.Array, n_voters: int, n: int):
    """words: (R, W) packed sign planes from R replicas → ±1 majority vote.

    This is SIMDRAM's TRA generalized to R inputs: per bit-lane popcount
    against R/2 (computed SWAR on the packed words, no unpacking on the
    wire)."""
    counts = jnp.zeros(words.shape[1:] + (32,), jnp.int32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    unpacked = ((words[:, :, None] >> shifts) & 1).astype(jnp.int32)
    counts = unpacked.sum(0)                     # (W, 32)
    maj = (2 * counts > n_voters).astype(jnp.float32) * 2 - 1
    return maj.reshape(-1)[:n]


def make_compressed_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                               mesh, sign_lr_scale: float = 1.0,
                               fused: bool = True, two_phase: bool = False,
                               exchange: str = "packed"):
    """shard_map step: per-DP-replica grads → error-feedback add → packed
    sign exchange over the data axes → bit-plane majority vote → update.
    Model-axis sharding stays under XLA's automatic partitioner (auto axes).

    ``fused=True`` (hillclimb H5): every gradient leaf is flattened into one
    vector so the whole exchange is a single packed collective.

    ``two_phase=True`` (hillclimb H7): instead of all-gathering R packed
    planes (whose per-device bytes grow with R), do the scalable exchange —
    all-to-all a 1/R slice of packed words to each voter, majority locally,
    all-gather only the majority result: per-device bytes ≈ 2·n/32 words
    independent of R (the reduce-scatter analogue for majority voting).

    ``exchange`` selects the vote collective: ``"packed"`` (all-gather of
    bit-packed sign planes, the true 32×-compressed wire format) or
    ``"psum"`` (sum of ±1 votes — the identical majority, since
    popcount(ones) > R/2 ⇔ Σ±1 > 0, but exchanged uncompressed; the
    dense-allreduce control for wire-byte comparisons).
    """
    loss_fn = make_loss_fn(cfg, loss_chunk=cfg.loss_chunk)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    # static voter count: the mesh is known at build time
    n_voters = 1
    for a in data_axes:
        n_voters *= int(mesh.shape[a])
    if exchange not in ("packed", "psum"):
        raise ValueError(f"unknown exchange mode {exchange!r}")

    def _psum_vote(gf):
        """Majority direction via Σ±1 (wire-uncompressed, vote-identical)."""
        votes = jnp.where(gf >= 0, jnp.int32(1), jnp.int32(-1))
        counts = jax.lax.psum(votes, data_axes)
        return (counts > 0).astype(jnp.float32) * 2 - 1

    def step(state: TrainState, batch):
        (_, metrics), grads = grad_fn(state.params, batch)
        # grads here are per-DP-shard (shard_map over data axes)

        def compress_one(g, e):
            gf = g.astype(jnp.float32) + e
            scale = jnp.mean(jnp.abs(gf))
            if exchange == "psum":
                maj = _psum_vote(gf.reshape(-1))
            else:
                packed = _pack_signs(gf)
                gathered = jax.lax.all_gather(packed, data_axes, tiled=False)
                gathered = gathered.reshape(n_voters, -1)
                maj = _majority_from_packed(gathered, n_voters, gf.size)
            maj = maj.reshape(g.shape)
            scale = jax.lax.pmean(scale, data_axes)
            decoded = (maj * scale).astype(jnp.float32)
            new_e = gf - decoded
            return decoded * sign_lr_scale, new_e

        flat_g, tdef = jax.tree.flatten(grads)
        flat_e = jax.tree.leaves(state.error_fb)
        if not fused:
            dec_err = [compress_one(g, e) for g, e in zip(flat_g, flat_e)]
            grads = jax.tree.unflatten(tdef, [d for d, _ in dec_err])
            new_err = jax.tree.unflatten(tdef, [e for _, e in dec_err])
        else:
            # ONE flat exchange: concat leaves → pack → single all-gather
            sizes = [g.size for g in flat_g]
            offs = np.cumsum([0] + sizes)
            gf = jnp.concatenate(
                [g.astype(jnp.float32).reshape(-1) + e.reshape(-1)
                 for g, e in zip(flat_g, flat_e)])
            scales = jnp.array(
                [jnp.mean(jnp.abs(gf[offs[i]:offs[i + 1]]))
                 for i in range(len(sizes))])
            scales = jax.lax.pmean(scales, data_axes)
            if exchange == "psum":
                maj = _psum_vote(gf)
            elif two_phase:
                packed = _pack_signs(gf)
                # pad so the word count splits evenly across voters
                w = packed.shape[0]
                pad = (-w) % n_voters
                packed = jnp.pad(packed, (0, pad))
                chunks = packed.reshape(n_voters, -1)
                # phase 1: voter j receives every replica's chunk j
                recv = jax.lax.all_to_all(chunks, data_axes, split_axis=0,
                                          concat_axis=0, tiled=True)
                recv = recv.reshape(n_voters, -1)
                slice_maj = _majority_from_packed(
                    recv, n_voters, recv.shape[1] * 32)          # ±1 slice
                # phase 2: gather the (repacked) majority slices
                slice_packed = _pack_signs(slice_maj)
                gathered = jax.lax.all_gather(slice_packed, data_axes,
                                              tiled=True)
                maj = _majority_from_packed(gathered[None, :], 1,
                                            gf.size + pad * 32)[:gf.size]
            else:
                packed = _pack_signs(gf)
                gathered = jax.lax.all_gather(packed, data_axes, tiled=False)
                maj = _majority_from_packed(gathered.reshape(n_voters, -1),
                                            n_voters, gf.size)
            scale_vec = jnp.concatenate(
                [jnp.full((s,), scales[i]) for i, s in enumerate(sizes)])
            decoded = maj * scale_vec
            new_e_flat = gf - decoded
            grads = jax.tree.unflatten(tdef, [
                (decoded[offs[i]:offs[i + 1]] * sign_lr_scale
                 ).reshape(flat_g[i].shape) for i in range(len(sizes))])
            new_err = jax.tree.unflatten(tdef, [
                new_e_flat[offs[i]:offs[i + 1]].reshape(flat_g[i].shape)
                for i in range(len(sizes))])
        params, opt, stats = adamw_update(opt_cfg, state.opt, state.params,
                                          grads)
        metrics.update(stats)
        metrics["loss"] = jax.lax.pmean(metrics["loss"], data_axes)
        return TrainState(params, opt, new_err), metrics

    return step, data_axes
