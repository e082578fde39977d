"""Error-feedback PLA-compressed cross-pod gradient reduction.

This is the paper's scenario (1) — "reduce transmissions between sensors
and the datacenter" — mapped onto the multi-pod mesh: each pod produces a
full (data+model reduced) gradient; instead of an fp32/bf16 all-reduce over
the slow cross-pod links, each pod PLA-compresses its gradient rows
(SingleStream-style ``(n, a, v)`` records with the paper's 256-point cap),
all-gathers the *records* over the ``pod`` axis, reconstructs and averages
locally.  The compression residual is carried in an error-feedback buffer
so training stays unbiased in expectation (Karimireddy et al. style EF).

Wire format per row (fixed budget K slots, shape-static for collectives):
``seg_end: uint8`` + ``(a, v): bfloat16`` = 5 bytes/slot, versus
``chunk * 4`` bytes raw — a fixed ≥ (chunk / (5K/4)) reduction, plus the
protocol-level accounting via :func:`repro.core.jax_pla.singlestream_nbytes`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.compat import sharding as compat_sharding
from repro.core.jax_pla import (PLARecords, angle_segment, decode_records,
                                linear_segment, propagate_lines, to_records,
                                singlestream_nbytes)

_SEGMENTERS = {"angle": angle_segment, "linear": linear_segment}


@dataclasses.dataclass(frozen=True)
class GradCompressionConfig:
    enabled: bool = True
    method: str = "angle"        # angle (O(1) state) | linear (best error)
    chunk: int = 256             # stream length (the paper's 1-byte cap)
    k_max: int = 32              # record slots per row (wire budget)
    eps_rel: float = 0.05        # eps = eps_rel * RMS(leaf)
    eps_ladder: int = 4          # per-row escalation: eps * 4^r, r < ladder
    min_leaf_size: int = 4096    # smaller leaves go uncompressed


def init_error_feedback(params) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _rows(flat: jax.Array, chunk: int) -> jax.Array:
    n = flat.shape[0]
    rows = -(-n // chunk)
    pad = rows * chunk - n
    return jnp.pad(flat, (0, pad)).reshape(rows, chunk)


def pla_compress_leaf(g: jax.Array, cfg: GradCompressionConfig,
                      eps_rows: jax.Array | None = None
                      ) -> Tuple[PLARecords, jax.Array]:
    """Compress one gradient leaf; returns (records, per-row eps used).

    Rows whose segmentation overflows the K-slot budget escalate eps by 4x
    (up to ``eps_ladder`` rungs) — the adaptive-threshold extension the
    paper's §8 singles out as the natural next step; leftover overflow is
    absorbed by error feedback.

    ``eps_rows``: per-row base eps.  Error-feedback callers MUST pass eps
    derived from the *raw* gradient (not grad+residual): residual-scaled
    eps inflates itself and the EF loop diverges linearly (measured —
    tests/test_compression.py::test_error_feedback_converges_unbiased).
    """
    flat = g.reshape(-1).astype(jnp.float32)
    y = _rows(flat, cfg.chunk)
    if eps_rows is not None:
        base_eps = eps_rows
    else:
        # Per-row eps: rows of very different magnitude (e.g. embedding
        # rows) each get eps_rel of their own RMS.
        base_eps = cfg.eps_rel * jnp.sqrt(jnp.mean(y * y, axis=1) + 1e-20)

    cands = []
    for r in range(cfg.eps_ladder):
        eps_r = base_eps * (4.0 ** r)
        seg = _SEGMENTERS[cfg.method](y, eps_r, max_run=cfg.chunk)
        cands.append((to_records(seg, cfg.k_max), eps_r))
    # Per-row: first rung that fits the budget (else last rung).
    rec, eps_row = cands[-1][0], jnp.full((y.shape[0],), cands[-1][1])
    for cand, eps_r in reversed(cands[:-1]):
        fit = ~cand.overflow
        take = lambda a, b: jnp.where(fit.reshape((-1,) + (1,) * (a.ndim - 1)),
                                      a, b)
        rec = PLARecords(take(cand.seg_end, rec.seg_end),
                         take(cand.a, rec.a), take(cand.v, rec.v),
                         jnp.where(fit, cand.count, rec.count),
                         jnp.where(fit, cand.overflow, rec.overflow))
        eps_row = jnp.where(fit, eps_r, eps_row)

    rec = PLARecords(
        seg_end=rec.seg_end.astype(jnp.uint8),
        a=rec.a.astype(jnp.float16),
        v=rec.v.astype(jnp.float16),
        count=rec.count.astype(jnp.uint8),
        overflow=rec.overflow,
    )
    return rec, eps_row


def overflow_escape_rows(g: jax.Array, rec: PLARecords,
                         cfg: GradCompressionConfig) -> jax.Array:
    """Raw copies of overflow rows (zeros elsewhere) — the escape hatch
    that keeps the eps guarantee *unconditional*.  Without it a single
    overflow row's garbage tail feeds the EF residual and the loop blows
    up exponentially (measured).  Wire accounting: chunk*4 bytes per
    overflow row (production uses ragged transfers; the dense zero-filled
    array here is a static-shape artifact of the collective)."""
    y = _rows(g.reshape(-1).astype(jnp.float32), cfg.chunk)
    return jnp.where(rec.overflow[:, None], y, 0.0)


def apply_escape(decoded_rows: jax.Array, rec: PLARecords,
                 raw_rows: jax.Array) -> jax.Array:
    return jnp.where(rec.overflow[:, None], raw_rows, decoded_rows)


def pla_decompress_leaf(rec: PLARecords, shape, cfg: GradCompressionConfig
                        ) -> jax.Array:
    rec32 = PLARecords(rec.seg_end.astype(jnp.int32),
                       rec.a.astype(jnp.float32),
                       rec.v.astype(jnp.float32),
                       rec.count.astype(jnp.int32), rec.overflow)
    y = decode_records(rec32, cfg.chunk)
    n = 1
    for s in shape:
        n *= s
    return y.reshape(-1)[:n].reshape(shape)


def _should_compress(path_leaf, cfg: GradCompressionConfig) -> bool:
    return path_leaf.size >= cfg.min_leaf_size


def pod_compressed_mean(grads, ef, cfg: GradCompressionConfig,
                        axis_name: str = "pod"):
    """Cross-pod mean of gradients with PLA compression + error feedback.

    Must run inside ``shard_map`` with ``axis_name`` manual.  ``grads`` and
    ``ef`` are this pod's local values; returns (mean_grads, new_ef,
    stats).  Leaves below ``min_leaf_size`` take a plain ``psum``.
    """
    n_pods = compat_sharding.axis_size(axis_name)

    def one(g, e):
        g_raw = g.astype(jnp.float32)
        if not cfg.enabled or g_raw.size < cfg.min_leaf_size:
            g = g_raw + e
            return jax.lax.pmean(g, axis_name), jnp.zeros_like(g), \
                jnp.zeros((), jnp.float32)
        # eps anchored to the *raw* gradient scale (EF stability).
        yr = _rows(g_raw.reshape(-1), cfg.chunk)
        eps_rows = cfg.eps_rel * jnp.sqrt(jnp.mean(yr * yr, axis=1) + 1e-20)
        g = g_raw + e
        rec, eps = pla_compress_leaf(g, cfg, eps_rows=eps_rows)
        raw_esc = overflow_escape_rows(g, rec, cfg)

        def dec_rows(r, esc):
            rec32 = PLARecords(r.seg_end.astype(jnp.int32),
                               r.a.astype(jnp.float32),
                               r.v.astype(jnp.float32),
                               r.count.astype(jnp.int32), r.overflow)
            from repro.core.jax_pla import decode_records
            return apply_escape(decode_records(rec32, cfg.chunk), r, esc)

        local_rows = dec_rows(rec, raw_esc)
        n = g.size
        local_dec = local_rows.reshape(-1)[:n].reshape(g.shape)
        new_ef = g - local_dec          # residual stays local (EF)
        # Exchange records (+ escape rows) over the pod axis.
        gathered = jax.lax.all_gather((rec, raw_esc), axis_name)
        decoded = jax.vmap(lambda re: dec_rows(*re))(gathered)
        mean = decoded.mean(axis=0).reshape(-1)[:n].reshape(g.shape)
        n_over = rec.overflow.sum()
        nbytes = jnp.float32(rec.seg_end.size + 2 * rec.a.size
                             + 2 * rec.v.size + rec.count.size) \
            + n_over * cfg.chunk * 4.0
        return mean, new_ef, nbytes

    flat, treedef = jax.tree.flatten(grads)
    ef_flat = jax.tree.flatten(ef)[0]
    outs = [one(g, e) for g, e in zip(flat, ef_flat)]
    mean = treedef.unflatten([o[0] for o in outs])
    new_ef = treedef.unflatten([o[1] for o in outs])
    wire_bytes = sum(o[2] for o in outs)
    raw_bytes = sum(jnp.full((), g.size * 4, jnp.float32) for g in flat)
    stats = {"wire_bytes": wire_bytes, "raw_bytes": raw_bytes,
             "n_pods": n_pods}
    return mean, new_ef, stats


def compression_report(grads, cfg: GradCompressionConfig) -> Dict[str, Any]:
    """Offline report: fixed-budget wire bytes + paper-protocol bytes +
    reconstruction error for each leaf (used by benchmarks)."""
    report = {}
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        name = jax.tree_util.keystr(path)
        if g.size < cfg.min_leaf_size:
            report[name] = {"raw_bytes": g.size * 4, "skipped": True}
            continue
        rec, eps = pla_compress_leaf(g, cfg)
        dec = pla_decompress_leaf(rec, g.shape, cfg)
        err = jnp.abs(dec - g.astype(jnp.float32)).max()
        rec32 = PLARecords(rec.seg_end.astype(jnp.int32),
                           rec.a.astype(jnp.float32),
                           rec.v.astype(jnp.float32),
                           rec.count.astype(jnp.int32), rec.overflow)
        proto_bytes = singlestream_nbytes(rec32, cfg.chunk).sum()
        report[name] = {
            "raw_bytes": int(g.size * 4),
            "fixed_wire_bytes": int(rec.seg_end.size + 2 * rec.a.size
                                    + 2 * rec.v.size + rec.count.size),
            "protocol_bytes": int(proto_bytes),
            "eps_base": float(eps.min()),
            "eps_max_used": float(eps.max()),
            "max_err": float(err),
            "overflow_rows": int(rec.overflow.sum()),
        }
    return report
