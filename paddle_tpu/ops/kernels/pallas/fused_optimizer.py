"""Fused optimizer megakernel: dtype-bucketed single-kernel updates.

Reference counterpart: the multi-tensor fused optimizer kernels under
`paddle/phi/kernels/fusion/` (fused_adam, multi_tensor_adam) — one kernel
sweep over a packed parameter group instead of a per-parameter launch
chain. Neptune-style (PAPERS.md) handwritten fusion for the training
tail XLA won't fuse across parameters on its own.

Design: the optimizer's parameter set is flattened into contiguous
per-(compute dtype, grad dtype, write-back dtype, weight-decay) buckets
(`plan_buckets`, planned ONCE per parameter structure — pure host
metadata, no device work). `fused_apply` then runs ONE Pallas kernel per
bucket that fuses the whole update chain: grad unscale (the GradScaler's
device-resident scale arrives as a traced reciprocal), global-norm clip
(the caller reduces the norm once across all buckets and passes the
coefficient), the anomaly-sentinel guarded select (every output lane
selects its input bitwise when `found`), the optimizer rule
(sgd/momentum/adam(+w)/lamb) with traced lr/step scalars, and the bf16
param write-back from fp32 masters — replacing O(params) kernel
launches with O(buckets).

Bitwise contract: the elementwise math here is EXACTLY the per-param
rules in `optimizer/optimizer.py` (`SGD._update` et al.) applied to the
concatenated flat buffer, so fused and per-param paths agree bitwise at
fp32. The only reductions (Lamb's per-layer trust-ratio norms) are
computed OUTSIDE the kernel on original-shaped segments so their
lowering matches the eager `jnp.sum(jnp.square(...))` exactly. All
scalar conditioning (unscale reciprocal, clip coefficient, sentinel
flag) is computed by the caller with the eager formulas and enters the
kernel through one SMEM scalar vector.

Off-TPU (and when `use_pallas=False`) the same shared math runs as an
XLA composite over the flat buckets — still one fused elementwise chain
per bucket, which is also how the eager (non-captured) optimizer path
batches its per-leaf updates: one layout implementation for both.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Optimizer rules with a fused kernel, and their state-slot layouts.
# Keys match optimizer.py's `_fused_kind_cfg` registry; anything else
# falls back to the per-param chain with a frozen reason.
STATE_KEYS: Dict[str, Tuple[str, ...]] = {
    "sgd": (),
    "momentum": ("velocity",),
    "adam": ("m", "v"),
    "lamb": ("m", "v"),
}

_LANES = 128
_BLOCK_ROWS = 512          # (512, 128) f32 tile = 256 KiB per operand
_SUBLANE_QUANTUM = 16      # rows quantum covering f32 (8) and bf16 (16)

# Tests force the pallas path in interpret mode (None = backend decides).
_FORCE_PALLAS: Optional[bool] = None


def default_use_pallas() -> bool:
    if _FORCE_PALLAS is not None:
        return _FORCE_PALLAS
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


class Bucket:
    """Contiguous flat layout for one (dtypes, weight-decay) group."""

    __slots__ = ("ids", "offsets", "sizes", "shapes", "total", "rows",
                 "block_rows", "cdtype", "gdtype", "low", "wd")

    def __init__(self, ids, offsets, sizes, shapes, cdtype, gdtype, low, wd):
        self.ids = tuple(ids)
        self.offsets = tuple(offsets)
        self.sizes = tuple(sizes)
        self.shapes = tuple(shapes)
        self.total = int(offsets[-1] + sizes[-1]) if sizes else 0
        rows = -(-max(self.total, 1) // _LANES)
        br = min(_BLOCK_ROWS, -(-rows // _SUBLANE_QUANTUM) * _SUBLANE_QUANTUM)
        self.rows = -(-rows // br) * br
        self.block_rows = br
        self.cdtype = cdtype
        self.gdtype = gdtype
        self.low = low
        self.wd = float(wd)


class BucketPlan:
    """The per-structure bucket layout for one optimizer instance."""

    __slots__ = ("kind", "cfg", "buckets", "state_keys", "n_params",
                 "_wd_devs")

    def __init__(self, kind: str, cfg: Dict, buckets: Sequence[Bucket],
                 n_params: int):
        self.kind = kind
        self.cfg = dict(cfg)
        self.buckets = tuple(buckets)
        self.state_keys = STATE_KEYS[kind]
        self.n_params = n_params
        self._wd_devs = None    # per-bucket traced-wd device scalars


def plan_buckets(kind: str, cfg: Dict, specs: Sequence[Tuple]) -> BucketPlan:
    """Lay out parameters into contiguous flat buckets.

    ``specs[k] = (shape, compute_dtype, grad_dtype, low_dtype_or_None,
    wd_float)`` for the k-th participating parameter. Pure host
    metadata: grouping key is (compute dtype, grad dtype, write-back
    dtype, weight-decay value), so every element of a bucket runs the
    IDENTICAL scalar chain and wd can be baked static per kernel.
    """
    groups: Dict[Tuple, List[int]] = {}
    for k, (shape, cdt, gdt, low, wd) in enumerate(specs):
        groups.setdefault((str(cdt), str(gdt),
                           None if low is None else str(low),
                           float(wd)), []).append(k)
    buckets = []
    for (cdt, gdt, low, wd), ids in sorted(groups.items(),
                                           key=lambda kv: kv[1][0]):
        offsets, sizes, shapes, off = [], [], [], 0
        for k in ids:
            shape = tuple(specs[k][0])
            size = int(np.prod(shape)) if shape else 1
            offsets.append(off)
            sizes.append(size)
            shapes.append(shape)
            off += size
        buckets.append(Bucket(ids, offsets, sizes, shapes,
                              cdt, gdt, low, wd))
    return BucketPlan(kind, cfg, buckets, len(specs))


# -- shared elementwise math --------------------------------------------------
# ONE implementation of each rule's element chain, applied by the Pallas
# kernel body to its VMEM tile and by the XLA composite to the whole
# flat bucket. The formulas mirror optimizer.py's `_update` rules
# line-for-line (including cast placement) so fused == per-param bitwise.

def _bias_inv(cfg: Dict, sv):
    """optimizer._bias_corrections as reciprocals. A Pallas body reads
    them from its scalar vector (``sv["inv_bc"]``, computed here by the
    caller): Mosaic has no scalar ``powf``."""
    if "inv_bc" in sv:
        return sv["inv_bc"]
    step = sv["step"].astype(jnp.float32)
    return jax.lax.optimization_barrier(
        (1.0 / (1.0 - cfg["b1"] ** step), 1.0 / (1.0 - cfg["b2"] ** step)))


def _condition_grad(g, pdtype, sv):
    """unscale + clip in the GRAD's dtype, then cast to the compute
    dtype — the exact order of GradScaler.unscale_ -> global-norm clip
    -> `_inline_update`'s `g.astype(p.dtype)`."""
    g = g * sv["inv"].astype(g.dtype)
    g = g * sv["coeff"].astype(g.dtype)
    return g.astype(pdtype) if g.dtype != pdtype else g


def _keep_old(found, old, new):
    # optimizer._guarded_update's per-leaf select: bitwise no-op on a
    # non-finite step, fuses into the elementwise chain (no cond barrier)
    return jax.lax.select(jnp.broadcast_to(found > 0, new.shape), old, new)


def _rule_elementwise(kind: str, cfg: Dict, p, g, state, sv,
                      condition: bool):
    """(new_p, new_state) for the purely elementwise rules, sentinel
    select applied. `g` is raw (pre-unscale/clip) in the grad dtype;
    wd rides the scalar vector (``sv["wd"]``). `condition` skips the
    unscale/clip multiplies entirely when nothing is folded — even the
    identity multiplies change FMA contraction downstream."""
    g = _condition_grad(g, p.dtype, sv) if condition \
        else (g.astype(p.dtype) if g.dtype != p.dtype else g)
    return _rule_core(kind, cfg, sv["wd"], p, g, state, sv)


def _rule_core(kind: str, cfg: Dict, wd32, p, g, state, sv):
    """The rule chain proper; `g` is already conditioned and in the
    compute dtype. `wd32` is an f32 scalar, traced on both routes (the
    per-param path passes wd as a program ARGUMENT, and a baked
    constant lets LLVM pick a different FMA contraction for `wd * p`,
    flipping low bits — the Pallas bodies read it from SMEM slot 5 for
    the same reason)."""
    lr = sv["lr"].astype(p.dtype)
    wd = wd32.astype(p.dtype)
    found = sv["found"]
    if kind == "sgd":
        gw = g + wd * p
        new_p, new_s = p - lr * gw, {}
    elif kind == "momentum":
        gw = g + wd * p
        v = cfg["momentum"] * state["velocity"] + gw
        upd = gw + cfg["momentum"] * v if cfg["nesterov"] else v
        new_p, new_s = p - lr * upd, {"velocity": v}
    elif kind == "adam":
        b1, b2, eps = cfg["b1"], cfg["b2"], cfg["eps"]
        if not cfg["decoupled"]:
            g = g + wd * p
        m = b1 * state["m"] + (1 - b1) * g
        v = b2 * state["v"] + (1 - b2) * jnp.square(g)
        inv_bc1, inv_bc2 = _bias_inv(cfg, sv)
        upd = (m * inv_bc1) / (jnp.sqrt(v * inv_bc2) + eps)
        if cfg["decoupled"]:
            upd = upd + wd * p
        new_p, new_s = p - lr * upd, {"m": m, "v": v}
    else:
        raise ValueError(f"no elementwise fused rule for {kind!r}")
    new_p = _keep_old(found, p, new_p)
    new_s = {k: _keep_old(found, state[k], v) for k, v in new_s.items()}
    return new_p, new_s


def _lamb_moments(cfg: Dict, p, g, state, sv, condition: bool):
    """Lamb phase 1: guarded new moments + RAW trust_ratio_div (its
    per-layer norms are reduced outside, on original-shaped segments)."""
    b1, b2, eps = cfg["b1"], cfg["b2"], cfg["eps"]
    g = _condition_grad(g, p.dtype, sv) if condition \
        else (g.astype(p.dtype) if g.dtype != p.dtype else g)
    wd = sv["wd"].astype(p.dtype)
    m = b1 * state["m"] + (1 - b1) * g
    v = b2 * state["v"] + (1 - b2) * jnp.square(g)
    inv_bc1, inv_bc2 = _bias_inv(cfg, sv)
    tr_div = (m * inv_bc1) / (jnp.sqrt(v * inv_bc2) + eps) + wd * p
    found = sv["found"]
    return (_keep_old(found, state["m"], m),
            _keep_old(found, state["v"], v), tr_div)


def _lamb_apply(p, tr_div, r, sv):
    """Lamb phase 2: p - lr*r*tr_div with the per-element trust ratio
    broadcast per segment, sentinel select applied."""
    lr = sv["lr"].astype(p.dtype)
    new_p = p - lr * r * tr_div
    return _keep_old(sv["found"], p, new_p)


# -- pallas kernels -----------------------------------------------------------

def _pack_scalars(cfg: Dict, sv) -> jax.Array:
    # [lr, step, inv, coeff, found, wd, 1/bc1, 1/bc2], one SMEM f32 vector
    z = jnp.float32(0.0)
    bc1, bc2 = _bias_inv(cfg, sv) if "b1" in cfg else (z, z)
    return jnp.stack([sv["lr"], sv["step"], sv["inv"], sv["coeff"],
                      sv["found"], sv["wd"], bc1, bc2])


def _unpack_scalars(ref) -> Dict[str, jax.Array]:
    return {"lr": ref[0], "step": ref[1], "inv": ref[2],
            "coeff": ref[3], "found": ref[4], "wd": ref[5],
            "inv_bc": (ref[6], ref[7])}


def _pad2d(flat, rows, dtype=None):
    total = flat.shape[0]
    if dtype is not None and flat.dtype != dtype:
        flat = flat.astype(dtype)
    pad = rows * _LANES - total
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, _LANES)


def _tiles(rows, br, n):
    spec = pl.BlockSpec((br, _LANES), lambda i, sv: (i, 0))
    return [spec] * n


def _bucket_kernel_call(body, bucket, inputs, out_dtypes):
    """Run `body` over (block_rows, 128) tiles of the bucket's flat 2-D
    buffers; one scalar-prefetch vector feeds every tile's SMEM."""
    rows, br = bucket.rows, bucket.block_rows
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // br,),
        in_specs=_tiles(rows, br, len(inputs) - 1),
        out_specs=_tiles(rows, br, len(out_dtypes)),
    )
    out_shape = [jax.ShapeDtypeStruct((rows, _LANES), d) for d in out_dtypes]
    return pl.pallas_call(
        body, grid_spec=grid_spec, out_shape=out_shape,
        name="fused_optimizer",
        interpret=_interpret())(*inputs)


def _pallas_elementwise_bucket(plan, bucket, pf, gf, sf, condition):
    """ONE kernel: conditioned grad -> rule -> guarded select -> (+ low
    write-back) over the whole bucket."""
    keys = plan.state_keys
    ns = len(keys)
    has_low = bucket.low is not None

    def body(sv_ref, p_ref, g_ref, *refs):
        s_in = refs[:ns]
        outs = refs[ns:]
        sv = _unpack_scalars(sv_ref)
        state = {k: r[...] for k, r in zip(keys, s_in)}
        new_p, new_s = _rule_elementwise(plan.kind, plan.cfg,
                                         p_ref[...], g_ref[...], state, sv,
                                         condition=condition)
        outs[0][...] = new_p
        for j, k in enumerate(keys):
            outs[1 + j][...] = new_s[k]
        if has_low:
            outs[1 + ns][...] = new_p.astype(outs[1 + ns].dtype)

    out_dtypes = [jnp.dtype(bucket.cdtype)] * (1 + ns)
    if has_low:
        out_dtypes.append(jnp.dtype(bucket.low))
    out = _bucket_kernel_call(
        body, bucket,
        [pf["svec"], pf["p"], gf] + [sf[k] for k in keys], out_dtypes)
    new_p = out[0].reshape(-1)[:bucket.total]
    new_s = {k: out[1 + j].reshape(-1)[:bucket.total]
             for j, k in enumerate(keys)}
    lowf = out[1 + ns].reshape(-1)[:bucket.total] if has_low else None
    return new_p, new_s, lowf


def _pallas_lamb_bucket(plan, bucket, pf, gf, sf, p_orig, condition):
    """Lamb as two bucket kernels around the (outside) per-layer norm
    reduction: moments+tr_div, then the trust-ratio apply."""
    keys = plan.state_keys
    svec, p2 = pf["svec"], pf["p"]
    cdt = jnp.dtype(bucket.cdtype)

    def body1(sv_ref, p_ref, g_ref, m_ref, v_ref, mo, vo, to):
        sv = _unpack_scalars(sv_ref)
        m, v, trd = _lamb_moments(plan.cfg, p_ref[...], g_ref[...],
                                  {"m": m_ref[...], "v": v_ref[...]}, sv,
                                  condition=condition)
        mo[...], vo[...], to[...] = m, v, trd

    m2, v2, t2 = _bucket_kernel_call(
        body1, bucket, [svec, p2, gf, sf["m"], sf["v"]], [cdt] * 3)
    trd_flat = t2.reshape(-1)[:bucket.total]
    r2 = _pad2d(_lamb_ratios(bucket, p_orig, trd_flat), bucket.rows)

    def body2(sv_ref, p_ref, t_ref, r_ref, po, *lo):
        sv = _unpack_scalars(sv_ref)
        new_p = _lamb_apply(p_ref[...], t_ref[...],
                            r_ref[...].astype(p_ref.dtype), sv)
        po[...] = new_p
        if lo:
            lo[0][...] = new_p.astype(lo[0].dtype)

    out_dtypes = [cdt] + ([jnp.dtype(bucket.low)] if bucket.low else [])
    out = _bucket_kernel_call(body2, bucket, [svec, p2, t2, r2], out_dtypes)
    new_p = out[0].reshape(-1)[:bucket.total]
    lowf = out[1].reshape(-1)[:bucket.total] if bucket.low else None
    new_s = {"m": m2.reshape(-1)[:bucket.total],
             "v": v2.reshape(-1)[:bucket.total]}
    return new_p, new_s, lowf


def _lamb_ratios(bucket, p_orig, trd_flat):
    """Per-layer trust ratios, broadcast per element. The norms reduce
    over ORIGINAL-shaped segments — same lowering as the eager rule's
    `jnp.sqrt(jnp.sum(jnp.square(...)))`, so the ratio is bitwise the
    eager one."""
    parts = []
    for p, off, sz, shp in zip(p_orig, bucket.offsets, bucket.sizes,
                               bucket.shapes):
        trd = jax.lax.slice_in_dim(trd_flat, off, off + sz, axis=0)
        # barrier mirrors Lamb._update's: both paths reduce a
        # materialized param-shaped array, so the reduction order (and
        # hence the ratio) agrees bitwise with the per-param rule
        trd = jax.lax.optimization_barrier(trd.reshape(shp))
        pn = jnp.sqrt(jnp.sum(jnp.square(p)))
        tn = jnp.sqrt(jnp.sum(jnp.square(trd)))
        r = jnp.where((pn > 0) & (tn > 0),
                      pn / jnp.where(tn > 0, tn, 1.0), 1.0)
        parts.append(jnp.broadcast_to(r.astype(jnp.float32), (sz,)))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


# -- composite (off-TPU / fallback) path --------------------------------------

def _lamb_segment(cfg: Dict, wd32, p, g, state, sv):
    """Lamb on one segment: optimizer.Lamb._update line-for-line (with
    its tr_div barrier), then the sentinel select. `wd32` is an f32
    scalar, traced on the composite route (see _rule_core)."""
    b1, b2, eps = cfg["b1"], cfg["b2"], cfg["eps"]
    lr = sv["lr"].astype(p.dtype)
    wd = wd32.astype(p.dtype)
    m = b1 * state["m"] + (1 - b1) * g
    v = b2 * state["v"] + (1 - b2) * jnp.square(g)
    inv_bc1, inv_bc2 = _bias_inv(cfg, sv)
    tr_div = (m * inv_bc1) / (jnp.sqrt(v * inv_bc2) + eps) + wd * p
    tr_div = jax.lax.optimization_barrier(tr_div)
    pn = jnp.sqrt(jnp.sum(jnp.square(p)))
    tn = jnp.sqrt(jnp.sum(jnp.square(tr_div)))
    r = jnp.where((pn > 0) & (tn > 0), pn / jnp.where(tn > 0, tn, 1.0), 1.0)
    new_p = p - lr * r * tr_div
    found = sv["found"]
    return _keep_old(found, p, new_p), \
        {"m": _keep_old(found, state["m"], m),
         "v": _keep_old(found, state["v"], v)}


def _composite_segments(plan, bucket, p_orig, g_orig, s_orig, sv,
                        condition: bool, wd32=None):
    """Off-TPU composite: the bucket's updates batch into the ONE
    ambient program, but each param's elementwise chain runs on its own
    original shape. Loop lengths then match the per-param path exactly,
    so LLVM's vectorization epilogue and FMA-contraction choices agree
    lane-for-lane and fp32 fused == per-param stays bitwise — a single
    flat loop puts segment tails into a different vector epilogue than
    the per-param loop and flips single lanes by 1 ulp. The flat layout
    serves the Pallas kernels; here the plan contributes the grouping,
    the shared scalar conditioning and the single executable."""
    if wd32 is None:
        wd32 = jnp.float32(bucket.wd)
    new_p, new_s, lows = [], [], []
    for p, g, s in zip(p_orig, g_orig, s_orig):
        if condition:
            # mirror the per-param ladder: GradScaler.unscale_ and the
            # global-norm clip each materialize the grads in a program
            # of their own, so the rule below must not contract across
            # those boundaries — the barriers reproduce them
            g = jax.lax.optimization_barrier(g * sv["inv"].astype(g.dtype))
            g = jax.lax.optimization_barrier(g * sv["coeff"].astype(g.dtype))
        if g.dtype != p.dtype:
            g = g.astype(p.dtype)
        if plan.kind == "lamb":
            new_pk, new_sk = _lamb_segment(plan.cfg, wd32, p, g, s, sv)
        else:
            new_pk, new_sk = _rule_core(plan.kind, plan.cfg, wd32,
                                        p, g, s, sv)
        new_p.append(new_pk)
        new_s.append(new_sk)
        lows.append(new_pk.astype(jnp.dtype(bucket.low))
                    if bucket.low else None)
    return new_p, new_s, lows


# -- bucketed apply -----------------------------------------------------------

def _gather(arrs):
    flat = [a.reshape(-1) for a in arrs]
    return jnp.concatenate(flat) if len(flat) > 1 else flat[0]


def fused_apply(plan: BucketPlan, p_list, g_list, s_list, lr, step,
                inv, coeff, found, use_pallas: bool = False,
                condition: bool = True, wd_list=None):
    """Apply the fused update. On the Pallas route each bucket's
    params/grads/state gather into contiguous flat buffers and ONE
    kernel per bucket runs the whole chain on (rows, 128) tiles; on the
    composite route the same bucket plan batches per-segment chains
    into the ambient program (see :func:`_composite_segments`).

    All arguments are traced arrays/scalars; `inv`/`coeff`/`found` are
    the caller-computed unscale reciprocal, global-norm clip coefficient
    and sentinel flag (1.0/1.0/0.0 when inactive — the in-kernel
    multiplies and select are then exact identities). `condition` says
    whether an unscale/clip is actually folded this step (the composite
    route skips the identity multiplies entirely then, matching the
    per-param program ladder). Returns ``(new_p tuple, new_state tuple,
    low_list)`` in the caller's parameter order, `low_list[k]` the
    bf16/f16 write-back for master params (None otherwise). `wd_list`
    optionally supplies one f32 weight-decay scalar per bucket — traced
    jit arguments on the eager route so `wd * p` lowers exactly like
    the per-param path's traced wd (None bakes the plan's values as
    trace constants, matching the captured per-param rule). The Pallas
    kernels read it from the scalar-prefetch vector either way.
    """
    def f32(x):
        return x.astype(jnp.float32) if hasattr(x, "astype") \
            else jnp.asarray(x, jnp.float32)

    sv = {"lr": f32(lr), "step": f32(step), "inv": f32(inv),
          "coeff": f32(coeff), "found": f32(found)}
    n = plan.n_params
    new_p: List = [None] * n
    new_s: List = [None] * n
    lows: List = [None] * n
    keys = plan.state_keys
    for bi, bucket in enumerate(plan.buckets):
        p_orig = [p_list[k] for k in bucket.ids]
        if not use_pallas:
            np_seg, ns_seg, low_seg = _composite_segments(
                plan, bucket, p_orig, [g_list[k] for k in bucket.ids],
                [s_list[k] for k in bucket.ids], sv, condition,
                None if wd_list is None else wd_list[bi])
            for j, k in enumerate(bucket.ids):
                new_p[k], new_s[k], lows[k] = np_seg[j], ns_seg[j], \
                    low_seg[j]
            continue
        p_flat = _gather(p_orig)
        g_flat = _gather([g_list[k] for k in bucket.ids])
        s_flat = {key: _gather([s_list[k][key] for k in bucket.ids])
                  for key in keys}
        wd32 = f32(wd_list[bi]) if wd_list is not None \
            else jnp.float32(bucket.wd)
        pf = {"svec": _pack_scalars(plan.cfg, dict(sv, wd=wd32)),
              "p": _pad2d(p_flat, bucket.rows)}
        gf = _pad2d(g_flat, bucket.rows)
        sf = {k: _pad2d(v, bucket.rows) for k, v in s_flat.items()}
        if plan.kind == "lamb":
            np_f, ns_f, low_f = _pallas_lamb_bucket(
                plan, bucket, pf, gf, sf, p_orig, condition)
        else:
            np_f, ns_f, low_f = _pallas_elementwise_bucket(
                plan, bucket, pf, gf, sf, condition)
        for k, off, sz, shp in zip(bucket.ids, bucket.offsets,
                                   bucket.sizes, bucket.shapes):
            new_p[k] = jax.lax.slice_in_dim(np_f, off, off + sz,
                                            axis=0).reshape(shp)
            new_s[k] = {key: jax.lax.slice_in_dim(ns_f[key], off, off + sz,
                                                  axis=0).reshape(shp)
                        for key in keys}
            if low_f is not None:
                lows[k] = jax.lax.slice_in_dim(low_f, off, off + sz,
                                               axis=0).reshape(shp)
    return tuple(new_p), tuple(new_s), lows
