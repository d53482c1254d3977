"""Distributed SGD — the canonical training loop.

Ref parity: flink-ml-lib/.../common/optimizer/SGD.java:67 (optimize:82,
TrainIterationBody:97, CacheDataAndDoTrain:157) + Optimizer.java. Semantics
reproduced exactly:

- per-task local batch: ``globalBatchSize/numTasks`` (+1 for the first
  ``globalBatchSize%numTasks`` tasks) sliced sequentially from the task's
  cached shard with wrap-to-zero at the end (SGD.java:206-213, 262-284 —
  including the short-batch-at-the-end behavior of ``subList(offset,
  min(offset+lb, n))``);
- per round: minibatch loss/gradient/weight sums all-reduced, then every
  task applies ``w -= lr/totalWeight · grad`` followed by regularization
  (SGD.java:231-243); the model update count equals the round count;
- termination: maxIter rounds, or all-reduced ``loss/totalWeight < tol``
  (TrainIterationBody criteria map). Note the criteria loss is the *data*
  loss only: the reference's regLoss bookkeeping (SGD.java:238-241) mutates
  a local copy of the received feedback that is zeroed before the next
  collect, so regLoss never reaches the criteria stream — we mirror that.

TPU design: the whole optimization is ONE compiled SPMD program — a
``lax.while_loop`` inside ``shard_map`` over the data axis. The reference's
per-round machinery (feedback channel, epoch alignment, chunked all-reduce
over TCP) becomes: carry in device registers/HBM, lockstep rounds, one
``psum`` over ICI per round. Zero host round-trips for the entire fit.
Compiled programs are cached per (loss, mesh, hyperparams); shapes are
handled by jit's own cache — repeated fits do not retrace.
"""

from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from flink_ml_tpu.common.metrics import ML_GROUP, metrics
from flink_ml_tpu.observability import health as _health
from flink_ml_tpu.observability.tracing import cold_build, tracer
from flink_ml_tpu.ops import sparse_window
from flink_ml_tpu.ops.losses import LossFunc
from flink_ml_tpu.ops.regularization import regularize
from flink_ml_tpu.parallel.mesh import (
    MODEL_AXIS,
    data_axes,
    data_pspec,
    data_shard_count,
    default_mesh,
    model_axis_of,
)
from flink_ml_tpu.parallel import mapreduce as mr
from flink_ml_tpu.parallel import update_sharding as _upd
from flink_ml_tpu.parallel.collective import ensure_on_mesh, replicate


@dataclasses.dataclass(frozen=True)
class SGDParams:
    """Ref: the SGDParams POJO consumed by SGD (SGD.java:67), extended
    with the stateful update rules (``method``): the reference's SGD is
    the stateless ``w -= lr/totalW · grad``; ``momentum`` and ``adam``
    carry per-coordinate moment accumulators through the fit — and
    under the cross-replica sharded update (update_sharding.py,
    arXiv:2004.13336) those accumulators live as ``1/N`` per-replica
    slices, which is the whole point: optimizer-state memory that
    scales DOWN with the mesh."""
    learning_rate: float = 0.1
    global_batch_size: int = 32
    max_iter: int = 20
    tol: float = 1e-6
    reg: float = 0.0
    elastic_net: float = 0.0
    #: update rule: "sgd" (stateless), "momentum", "adam"
    method: str = "sgd"
    momentum: float = 0.9
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8


#: moment VECTORS each rule carries (adam additionally carries the
#: scalar step counter for bias correction — see _opt_init)
_OPT_VECTORS = {"sgd": 0, "momentum": 1, "adam": 2}


def _check_method(prm: SGDParams) -> None:
    if prm.method not in _OPT_VECTORS:
        raise ValueError(
            f"SGDParams.method must be one of {sorted(_OPT_VECTORS)}, "
            f"got {prm.method!r}")


def _update_rule(prm: SGDParams, xp=jnp):
    """The per-coordinate update rule ``rule(grad_sum, total_w, w, opt)
    -> (w_new, opt_new)`` — elementwise along dim 0, so the SAME
    callable applies to the full replicated vector and to a replica's
    ``1/N`` slice under the sharded update, and (with ``xp=np``) to the
    host CSR path, keeping dense/sparse/sharded fits numerically
    aligned by construction. ``opt`` is the rule's moment state: ``()``
    for sgd, ``(m,)`` for momentum, ``(m, v, t)`` for adam (t is the
    replicated bias-correction step counter — never sliced).
    Regularization is applied by the caller AFTER the rule
    (SGD.java:231-243 order, shared by every method)."""
    _check_method(prm)
    lr = prm.learning_rate
    if prm.method == "sgd":
        def rule(grad, total_w, w, opt):
            # the exact historical expression — the replicated sgd path
            # must stay bit-identical to the pre-stateful programs
            return w - (lr / xp.maximum(total_w, 1e-30)) * grad, opt
    elif prm.method == "momentum":
        mu = prm.momentum

        def rule(grad, total_w, w, opt):
            g = grad / xp.maximum(total_w, 1e-30)
            m = mu * opt[0] + g
            return w - lr * m, (m,)
    else:  # adam
        b1, b2, eps = prm.beta1, prm.beta2, prm.eps

        def rule(grad, total_w, w, opt):
            g = grad / xp.maximum(total_w, 1e-30)
            m, v, t = opt
            t = t + 1.0
            m = b1 * m + (1.0 - b1) * g
            v = b2 * v + (1.0 - b2) * (g * g)
            m_hat = m / (1.0 - b1 ** t)
            v_hat = v / (1.0 - b2 ** t)
            return w - lr * m_hat / (xp.sqrt(v_hat) + eps), (m, v, t)
    return rule


def _opt_specs(prm: SGDParams, wspec, spec0, sharded: bool):
    """shard_map in/out specs for the opt-state tuple: moment vectors
    follow the coefficient placement — replicated (or model-sharded
    under TP) normally, dim-0-sharded ``1/N`` slices under the sharded
    update (they never all-gather: this is the 1/N memory) — and adam's
    step counter is always a replicated scalar."""
    vec = P(spec0) if sharded else wspec
    specs = (vec,) * _OPT_VECTORS[prm.method]
    if prm.method == "adam":
        specs = specs + (P(),)
    return specs


def _sgd_update_math(loss_func, prm: SGDParams, axes, model_axis=None,
                     sharded: bool = False):
    """The post-slice math of one round — loss/gradient on the minibatch,
    the fused [grad, weight, loss] reduction (the reference's
    feedbackArray layout, SGD.java:190), the model update +
    regularization (SGD.java:231-243) — shared by the while-loop and
    host-driven programs so a change here propagates to every fit path.

    Returns ``update(coeffs, opt, products, yb, wb) ->
    (new_coeffs, new_opt, mean_loss)``: the local [grad | weight | loss]
    partials of the minibatch, their cross-shard reduction and the model
    update. ``products`` is the batch window's ``(margins, gradient)``:
    ``margins(coeffs)`` the rows' dots and ``gradient(multipliers)`` the
    local gradient sum, the one part a dense window
    (:func:`_dense_products`) and a sparse one
    (``sparse_window.products``) differ in (:func:`_sgd_round_math`).
    ``opt`` is the stateful rule's moment tuple (:func:`_update_rule`):
    ``()`` for plain sgd, so the stateless programs carry nothing. Must
    be called inside a ``mapreduce.map_shards`` body over the mesh's
    data ``axes``.

    With ``sharded`` (update_sharding.py, DP meshes only) the tail is
    the cross-replica sharded update: the gradient reduce-scatters so
    each replica updates only its own ``1/N`` coefficient slice
    (regularization included — it is elementwise), then the fresh
    coefficients all-gather — while the moment slices (momentum's m,
    adam's m/v) STAY sharded across rounds, the 1/N optimizer memory of
    arXiv:2004.13336; the scalar [weight | loss] tail still
    all-reduces. The coefficient carry must be padded to the shard
    multiple (``optimize`` does). Results match the replicated tail up
    to float reassociation in the reduction order."""
    rule = _update_rule(prm)

    def update(coeffs, opt, products, yb, wb):
        # LossFunc.loss_and_gradient, spelled out so that the window's
        # two products carry their names into the device trace
        margins, gradient = products
        dots = margins(coeffs)
        loss_sum, multipliers = loss_func.terms(dots, yb, wb)
        grad_sum = gradient(multipliers)
        packed_local = jnp.concatenate([
            grad_sum, jnp.sum(wb)[None].astype(grad_sum.dtype),
            loss_sum[None]])
        if sharded:
            with jax.named_scope("sgd.grad_allreduce"):
                tail = mr.reduce_sum(packed_local[-2:], axes)
            total_w, total_loss = tail[0], tail[1]
            grad_pad = _upd.pad_leading(packed_local[:-2], coeffs.shape[0])

            def apply_fn(g_slice, c_slice, opt_state):
                upd, new_opt = rule(g_slice, total_w, c_slice, opt_state)
                upd, _ = regularize(upd, prm.reg, prm.elastic_net,
                                    prm.learning_rate)
                return upd, new_opt

            updated, new_opt = _upd.sharded_apply(axes, grad_pad, coeffs,
                                                  opt, apply_fn)
        else:
            with jax.named_scope("sgd.grad_allreduce"):
                packed = mr.reduce_sum(packed_local, axes)
            grad, total_w, total_loss = packed[:-2], packed[-2], packed[-1]

            # ref updateModel (SGD.java:231-243); skip when no weight
            updated, new_opt = rule(grad, total_w, coeffs, opt)
            updated, _ = regularize(updated, prm.reg, prm.elastic_net,
                                    prm.learning_rate)
        coeffs_out = jnp.where(total_w > 0, updated, coeffs)
        # a zero-weight round must leave the moments untouched too
        opt_out = jax.tree_util.tree_map(
            lambda n, o: jnp.where(total_w > 0, n, o), new_opt, opt)
        mean_loss = total_loss / jnp.maximum(total_w, 1e-30)
        return coeffs_out, opt_out, mean_loss

    return update


def _dense_products(xl, start, rows: int, model_axis=None):
    """The two products of a dense batch window, the task's ``rows`` at
    ``start``: under the on-chip gate the window as the table lies,
    ``(d, rows)`` rows in lanes (a bitcast of the column-major table),
    materialised once, so XLA places it on chip and both products read it
    there: the batch crosses HBM once a round, not once a product. No
    control flow around it: inside a loop XLA carries the table row-major,
    a copy of its size. Past the gate each product reads ``(rows, d)``
    where it lies. Under TP ``d`` is the local feature shard: the margins
    are partial dots added over the model axis, the gradient stays
    local."""
    onchip = _batch_onchip(rows, xl.shape[1])
    if onchip:
        xb = jax.lax.optimization_barrier(
            jax.lax.dynamic_slice_in_dim(xl.T, start, rows, axis=1))
    else:
        xb = jax.lax.dynamic_slice_in_dim(xl, start, rows, axis=0)

    def margins(coeffs):
        with jax.named_scope("sgd.margins"):
            # d == coeffs length unless sharded padding
            d = xb.shape[0 if onchip else 1]
            w = coeffs if model_axis is not None else coeffs[:d]
            dots = w @ xb if onchip else xb @ w
            if model_axis is not None:
                dots = mr.reduce_sum(dots, model_axis)
        return dots

    def gradient(multipliers):
        with jax.named_scope("sgd.gradient"):
            # local feature shard under TP
            return xb @ multipliers if onchip else xb.T @ multipliers

    return margins, gradient


def _sparse_products(xl, start, rows: int, sparse):
    """The two products of a sparse batch window: ``xl`` is a task's
    ``(ids, values)``, each ``(local_n, k)`` as the column lies (and with a
    narrow index the column's dictionaries, whole on every task), and the
    window their ``(k, rows)`` slice at ``start`` (a bitcast of the
    column-major arrays, as the dense window is). Under the on-chip gate
    both slices are made once, as the dense window is, and both products
    read them there."""
    window = tuple(jax.lax.dynamic_slice_in_dim(a.T, start, rows, axis=1)
                   for a in xl[:2])
    if _batch_onchip(2 * rows, xl[0].shape[1]):
        window = jax.lax.optimization_barrier(window)
    return sparse_window.products(*window, sparse.size, sparse.hot,
                                  sparse.narrow, *xl[2:])


#: the largest batch window, in padded bytes, that a round brings on chip
#: once (:func:`_batch_onchip`). Read from the TPU compiler ahead of time
#: for a v5e (``scripts/round_forms.py --gate``): XLA keeps the made window
#: in on-chip memory (``S(1)``) up to 117.4 MB at d 8, 100 and 512 (282,240
#: rows at d 100) and writes it to HBM past that; 64 MiB keeps 1.75x under
ONCHIP_BATCH_BYTES = 64 << 20


def _local_batch(prm: SGDParams, p: int, local_n: int) -> int:
    """The rows of a task's batch window: its share of the global batch,
    the first ``global % p`` tasks' share, at most the shard."""
    gb = prm.global_batch_size
    return min(gb // p + (1 if gb % p else 0), local_n)


def _batch_onchip(rows: int, d: int) -> bool:
    """Whether a batch window of ``rows`` x ``d`` (the local feature shard)
    is read from HBM once a round: its padded float32 bytes, ``d`` to the
    sublane tile of 8, are under :data:`ONCHIP_BATCH_BYTES`. Both forms
    compute the same products; past the budget XLA would write the window
    to HBM and read it back twice, so the two products read the table
    instead."""
    return rows * (-(-d // 8) * 8) * 4 <= ONCHIP_BATCH_BYTES


def _sgd_round_math(loss_func, prm: SGDParams, p: int, axes,
                    model_axis=None, sharded: bool = False,
                    weighted: bool = True, n_valid: Optional[int] = None,
                    sparse: Optional[sparse_window.Layout] = None):
    """The per-shard math of ONE training round — shared verbatim by the
    all-device while_loop program and the host-driven round program so the
    two modes stay numerically identical by construction.

    Returns ``round(xl, yl, wl, coeffs, opt, offset) ->
    (coeffs, opt, new_offset, mean_loss)`` operating on this shard's
    slice; must be called inside shard_map over the mesh's data axes
    (``axes`` — a flat ("data",) mesh or a ("dcn", "data") hybrid).

    Without ``weighted`` (a fit with no weight column) ``wl`` is ``None``
    and every row of the batch weighs 1: the batch weight is the round's
    own validity mask. ``n_valid`` is then the true row count when the
    inputs were zero-padded to divide over the data axes (``None`` when
    nothing was padded): the padded rows weigh 0, as a padded weight
    column's would.

    With ``model_axis`` (tensor parallelism for wide models — a TPU-native
    capability beyond the reference's DP-only design), the feature
    dimension of ``xl`` and ``coeffs`` is additionally sharded over that
    axis: the per-sample margins are partial dots psum'd over the model
    axis (every loss here is margin-decomposable, LossFunc.terms), the
    gradient matvec and the coefficient update stay local to the feature
    shard, and the loss/weight reduction crosses the data axes only.

    With ``sparse`` (a ``DeviceSparseColumn``'s ``size`` and ``hot``
    index) ``xl`` is the column's ``(ids, values)`` pair and the window's
    products are the sparse ones (:func:`_sparse_products`); the schedule,
    the validity mask, the update and the carry are this same code."""
    gb = prm.global_batch_size
    lb_base, lb_rem = gb // p, gb % p
    update = _sgd_update_math(loss_func, prm, axes, model_axis,
                              sharded=sharded)

    @jax.named_scope("sgd.round")
    def round_step(xl, yl, wl, coeffs, opt, offset):
        values = xl if sparse is None else xl[1]
        local_n = values.shape[0]  # static at trace time
        lb_max = _local_batch(prm, p, local_n)
        task_id = mr.shard_index(axes)
        # ref SGD.java:206-213 — low task ids take the remainder
        lb = jnp.minimum(lb_base + (task_id < lb_rem).astype(jnp.int32),
                         local_n)

        # minibatch slice with clip-at-end + wrap-to-zero (the reference's
        # contiguous subList, SGD.java:262-284) as ONE dynamic-slice DMA
        # instead of a row gather — a contiguous HBM window, not per-row
        # addressing. dynamic_slice clamps its start to keep the window
        # in bounds, so validity is remapped to SOURCE rows: rows outside
        # [offset, offset+lb) ∩ [0, local_n) get weight 0, and the
        # weight-scaled losses (losses.py terms — loss and multipliers
        # are both `weights * ...`) zero their loss and gradient exactly;
        # the batch values themselves need no masking.
        start = jnp.minimum(offset, local_n - lb_max)
        products = (_dense_products(xl, start, lb_max, model_axis)
                    if sparse is None
                    else _sparse_products(xl, start, lb_max, sparse))
        yb = jax.lax.dynamic_slice_in_dim(yl, start, lb_max, axis=0)
        ws = (jax.lax.dynamic_slice_in_dim(wl, start, lb_max, axis=0)
              if weighted else None)
        src = start + jnp.arange(lb_max)
        valid = jnp.logical_and(src >= offset, src < offset + lb)
        if weighted:
            wb = ws * valid.astype(values.dtype)
        else:
            if n_valid is not None:
                # the rows ensure_on_mesh padded on weigh nothing
                valid = jnp.logical_and(
                    valid, task_id * local_n + src < n_valid)
            wb = valid.astype(values.dtype)

        coeffs, opt, mean_loss = update(coeffs, opt, products, yb, wb)
        new_offset = jnp.where(offset + lb >= local_n, 0, offset + lb)
        return coeffs, opt, new_offset, mean_loss

    return round_step


def _table_spec(spec0, model_axis, sparse):
    """The table operand's spec: rows over the data axes (features over
    the model axis under TP), or a sparse column's two arrays, rows over
    the data axes, and with a narrow index its dictionaries, whole on every
    task."""
    if sparse is None:
        return P(spec0, model_axis)
    return (P(spec0, None), P(spec0, None)) + ((P(),) if sparse.narrow
                                               else ())


@functools.lru_cache(maxsize=128)
@cold_build("sgd_segment")
def _build_sgd_segment_program(loss_cls, mesh: Mesh, prm: SGDParams,
                               health: bool = False,
                               sharded: bool = False,
                               fused: bool = False,
                               weighted: bool = True,
                               n_valid: Optional[int] = None,
                               fresh: bool = False,
                               sparse: Optional[sparse_window.Layout] = None):
    """A K-round slice of the training loop as ONE compiled SPMD program:
    ``segment(xs, ys, ws, coeffs, offsets, opt, epoch0, limit, hist,
    fin) -> (coeffs, offsets, opt, mean_loss, epoch, stop, hist, fin)``.
    The epoch bounds are device scalars, so every segment of a
    checkpointed fit reuses a single compilation; between segments the
    host snapshots the carry (iteration.run_segmented) — fault tolerance
    at fast-path speed, the composition the reference gets from
    checkpointing *through* the iteration (Checkpoints.java:43).

    ``opt`` is the stateful rule's moment tuple (:func:`_update_rule`):
    ``()`` for plain sgd — the stateless signature carries nothing — and
    (m,) / (m, v, t) for momentum / adam, donated with the carry; under
    the sharded update the moment vectors are dim-0-sharded ``1/N``
    slices that never leave their replicas between rounds
    (arXiv:2004.13336 — the 1/N optimizer memory).

    The plain (uncheckpointed) fit is the degenerate call
    ``segment(..., epoch0=0, limit=max_iter)`` from a carry of zeros, and
    with ``fresh`` the program makes that start itself:
    ``segment(xs, ys, ws, coeffs)``, same outputs. The offsets, the
    moments (zeros of the local shape their ``opt_specs`` say), adam's
    step and the two bounds are built inside, before the same ``run``, so
    the caller hands over the one thing that carries information — the
    coefficients, as the host array they are — and places nothing. A
    carry that crosses the host (a checkpointed fit's, a restore's) keeps
    the form above; both forms wrap ONE loop, so the two paths cannot
    drift numerically. ``fresh`` is for health-off builds: a health-armed
    fit keeps its ``hist``/``fin`` operands.

    Without ``weighted`` the fit has no weight column: ``ws`` is ``None``,
    the program takes no weight operand and a row's weight is its
    validity in the round's batch (:func:`_sgd_round_math`, where
    ``n_valid`` is explained too). With ``sparse`` ``xs`` is a device
    sparse column's ``(ids, values)`` pair, each row-sharded as a dense
    table is (and ``dicts``, replicated, where ``sparse.narrow``), and every
    round's products are the sparse window's (the same function): the
    carries, the fresh start and the outputs do not change.

    With ``health`` (observability/health.py), the signature grows two
    trailing carries and each round writes its ``(loss, update norm,
    param norm)`` convergence row into the ``hist`` buffer (a replicated
    ``(max_iter, 3)`` carry — the DrJAX-style first-class numeric
    output) and folds ONE non-finite sentinel scalar into ``fin``; the
    host reads both only at segment boundaries, so telemetry adds zero
    extra device syncs.

    With ``fused`` (iteration.segment_fusion_enabled) the per-boundary
    scalars come back STACKED as one int32 vector — ``[epoch, stop]``,
    or ``[epoch, stop, fin]`` with health — so the host pays ONE
    device→host transfer per segment boundary instead of one per
    scalar; the outputs become ``(coeffs, offsets, opt, mean_loss,
    bundle)`` (+ ``hist`` with health). The (coeffs, offsets, opt)
    carry — and the hist buffer with health — is DONATED in every build
    (the in-place update of the raw-speed ladder); sharded builds
    additionally route through ``instrumented_jit`` via their name for
    per-function compile accounting."""
    axes = data_axes(mesh)
    spec0 = data_pspec(mesh)
    p = data_shard_count(mesh)
    model_axis = model_axis_of(mesh)
    wspec = P(model_axis) if model_axis else P()
    round_step = _sgd_round_math(loss_cls(), prm, p, axes, model_axis,
                                 sharded=sharded, weighted=weighted,
                                 n_valid=n_valid, sparse=sparse)
    opt_specs = _opt_specs(prm, wspec, spec0, sharded)

    def run(xl, yl, wl, coeffs, offsets, opt, epoch0, limit, hist, fin):
        def cond(state):
            epoch, stop = state[4], state[5]
            return jnp.logical_and(epoch < limit, jnp.logical_not(stop))

        def step(state):
            coeffs, offset, opt, _, epoch, _, hist, fin = state
            new_coeffs, new_opt, new_offset, mean_loss = round_step(
                xl, yl, wl, coeffs, opt, offset)
            if health:
                row, row_fin = _health.convergence_row(
                    mean_loss, coeffs, new_coeffs, model_axis)
                hist = jax.lax.dynamic_update_slice(
                    hist, row[None], (epoch, jnp.int32(0)))
                fin = jnp.logical_and(fin, row_fin)
            return (new_coeffs, new_offset, new_opt, mean_loss,
                    epoch + 1, mean_loss < prm.tol, hist, fin)

        init = (coeffs, offsets[0], opt,
                jnp.asarray(jnp.inf, coeffs.dtype),
                epoch0, jnp.asarray(False), hist, fin)
        coeffs, offset, opt, mean_loss, epoch, stop, hist, fin = \
            jax.lax.while_loop(cond, step, init)
        return (coeffs, offset[None], opt, mean_loss, epoch, stop, hist,
                fin)

    if health:
        def sgd_segment(xl, yl, wl, coeffs, offsets, opt, epoch0, limit,
                        hist, fin):
            out = run(xl, yl, wl, coeffs, offsets, opt, epoch0, limit,
                      hist, fin)
            if not fused:
                return out
            coeffs, offsets, opt, mean_loss, epoch, stop, hist, fin = out
            bundle = jnp.stack([epoch, stop.astype(jnp.int32),
                                fin.astype(jnp.int32)])
            return coeffs, offsets, opt, mean_loss, bundle, hist

        extra_in = (P(), P())
        extra_out = (P(),) if fused else (P(), P())
        donate = (3, 4, 5, 8)
    else:
        def sgd_segment(xl, yl, wl, coeffs, offsets, opt, epoch0, limit):
            out = run(xl, yl, wl, coeffs, offsets, opt, epoch0, limit,
                      jnp.zeros((0, 3), jnp.float32),
                      jnp.asarray(True))[:6]
            if not fused:
                return out
            coeffs, offsets, opt, mean_loss, epoch, stop = out
            bundle = jnp.stack([epoch, stop.astype(jnp.int32)])
            return coeffs, offsets, opt, mean_loss, bundle

        extra_in, extra_out = (), ()
        donate = (3, 4, 5)

    carry_in = (P(spec0), opt_specs, P(), P()) + extra_in
    if fresh:
        if health:
            raise ValueError("a health-armed fit starts from its carry")
        from_carry = sgd_segment

        def sgd_segment(xl, yl, wl, coeffs):  # noqa: F811 — the one name
            local = coeffs.shape[0] // p if sharded else coeffs.shape[0]
            opt = tuple(jnp.zeros((local,), coeffs.dtype)
                        for _ in range(_OPT_VECTORS[prm.method]))
            if prm.method == "adam":
                opt = opt + (jnp.zeros((), coeffs.dtype),)
            return from_carry(xl, yl, wl, coeffs, jnp.zeros((1,), jnp.int32),
                              opt, jnp.int32(0), jnp.int32(prm.max_iter))

        carry_in, donate = (), (3,)

    scalar_out = (P(),) if fused else (P(), P())
    return mr.map_shards(
        sgd_segment, mesh,
        in_specs=(_table_spec(spec0, model_axis, sparse), P(spec0), P(spec0),
                  wspec) + carry_in,
        out_specs=(wspec, P(spec0), opt_specs, P()) + scalar_out
        + extra_out,
        donate_argnums=donate,
        name="sgd.segment" if sharded else None)


@functools.lru_cache(maxsize=128)
def _build_sgd_round_program(loss_cls, mesh: Mesh, prm: SGDParams,
                             sharded: bool = False,
                             weighted: bool = True,
                             n_valid: Optional[int] = None,
                             sparse: Optional[sparse_window.Layout] = None):
    """ONE training round as a compiled mapped program — the building
    block of the checkpointable host loop (iterate_bounded calls it as it
    is: nothing is jitted per fit). Wraps the same _sgd_round_math as the
    all-device program, so device and host modes are numerically
    identical by construction (``weighted``, ``n_valid`` and ``sparse``
    as there)."""
    axes = data_axes(mesh)
    spec0 = data_pspec(mesh)
    p = data_shard_count(mesh)
    model_axis = model_axis_of(mesh)
    wspec = P(model_axis) if model_axis else P()
    round_step = _sgd_round_math(loss_cls(), prm, p, axes, model_axis,
                                 sharded=sharded, weighted=weighted,
                                 n_valid=n_valid, sparse=sparse)
    opt_specs = _opt_specs(prm, wspec, spec0, sharded)

    def sgd_round(xl, yl, wl, coeffs, offsets, opt):
        coeffs, opt, new_offset, mean_loss = round_step(
            xl, yl, wl, coeffs, opt, offsets[0])
        return coeffs, new_offset[None], mean_loss, opt

    return mr.map_shards(
        sgd_round, mesh,
        in_specs=(_table_spec(spec0, model_axis, sparse), P(spec0),
                  P(spec0), wspec, P(spec0), opt_specs),
        out_specs=(wspec, P(spec0), P(), opt_specs))


@functools.lru_cache(maxsize=128)
def _tp_prepare_program(rem: int, pad_d: int, sharding):
    """Compiled cast+pad for a device-resident feature matrix entering the
    tensor-parallel layout (rows to the data axes, features to the model
    axis) — no host round-trip."""

    def prepare_rows(a):
        a = a.astype(jnp.float32)
        if rem or pad_d:
            a = jnp.pad(a, ((0, rem), (0, pad_d)))
        return a

    return jax.jit(prepare_rows, out_shardings=sharding)


@functools.lru_cache(maxsize=128)
def _health_hist_program(rows: int, sharding):
    """Compiled maker of the NaN-filled ``(rows, 3)`` convergence history
    a health-armed segmented fit starts from (each call a fresh buffer:
    the segment program donates it). Built under jit, not device_put:
    putting a host NaN array onto a multi-process sharding trips jax's
    cross-process value check (NaN != NaN in
    multihost_utils.assert_equal)."""

    def sgd_health_hist():
        return jnp.full((rows, 3), jnp.nan, jnp.float32)

    return jax.jit(sgd_health_hist, out_shardings=sharding)


def _health_tag(loss_func: LossFunc, tag: Optional[str]) -> str:
    if tag:
        return tag
    name = getattr(type(loss_func), "NAME", None)
    return f"SGD[{name or type(loss_func).__name__}]"


def _finish_fit_health(algo: str, health_on: bool, hist, fin, epochs,
                       mean_loss, coeffs_host, epoch0: int = 0) -> None:
    """The shared health tail of every SGD fit path: with telemetry
    armed, record the executed slice of the device-produced convergence
    history and classify divergence (raising the terminal NonFiniteState
    when the in-program sentinel tripped); otherwise run the cheap
    always-on guard over the already-fetched final state."""
    if health_on and hist is not None:
        h = np.asarray(hist, np.float64)
        lo = min(int(epoch0), h.shape[0])
        hi = min(int(epochs), h.shape[0])
        _health.check_fit(
            algo, {"loss": h[lo:hi, 0], "updateNorm": h[lo:hi, 1],
                   "paramNorm": h[lo:hi, 2]},
            finite=bool(fin), epoch0=lo)
    else:
        _health.guard_final_state(algo, coeffs_host, loss=mean_loss)


def _batch_form(prm: SGDParams, mesh: Mesh, n: int, d: int,
                arrays: int = 1) -> str:
    """``"onchip"`` where a fit's rounds read their batch from HBM once
    (:func:`_batch_onchip` of a task's window over its shard of ``n`` rows
    and of ``d`` features, in each of ``arrays`` arrays: a sparse column's
    window is its ids' and its values'), else ``"hbm"``: the ``batch``
    attribute of ``sgd.optimize`` and ``sgd.launch``."""
    p = data_shard_count(mesh)
    model_axis = model_axis_of(mesh)
    tp = int(mesh.shape[model_axis]) if model_axis else 1
    rows = _local_batch(prm, p, -(-n // p))
    return "onchip" if _batch_onchip(arrays * rows, -(-d // tp)) else "hbm"


def _count_batch_reads(batch: str, rounds: int, entries: int = 0,
                       dict_entries: int = 0) -> int:
    """``ml.sgd batchReads``: the HBM reads of a round's batch a fit made,
    one a round on chip, one a product (two) past the gate, which it
    returns; and for a sparse fit ``sparseEntries``: the ``entries`` its
    rounds' windows hold, each gathered once and scattered once (or summed
    as a column), and ``dictEntries``: the ``dict_entries`` of those the
    dictionary form took."""
    group = metrics.group(ML_GROUP, "sgd")
    reads = rounds * (1 if batch == "onchip" else 2)
    group.counter("batchReads", reads)
    if entries:
        group.counter("sparseEntries", rounds * entries)
        group.counter("dictEntries", rounds * dict_entries)
    return reads


#: program -> {abstract signature: its gradient's operations}
_GRADIENT_OPS = weakref.WeakKeyDictionary()


def _note_gradient_ops(span, prog, sparse, *args) -> None:
    """Name on a recording ``sgd.launch`` span of a sparse fit, as
    ``gradient_ops``, the operations its compiled program runs for the
    gradient (``sparse_window.gradient_ops``): read once a program and
    signature from the executable the call dispatches (``lower`` and
    ``compile`` of the call's own operands find it in jit's caches), and
    never where no span records."""
    if sparse is None or not tracer.active:
        return
    from flink_ml_tpu.observability.compilestats import abstract_signature

    known = _GRADIENT_OPS.setdefault(prog, {})
    sig = abstract_signature(args)
    if sig not in known:
        jitted = getattr(prog, "_jitted", prog)
        known[sig] = sparse_window.gradient_ops(
            jitted.lower(*args).compile().as_text())
    span.set_attribute("gradient_ops", known[sig])


#: ``last_execution_path`` of a sparse fit, by the dense path it shares
_SPARSE_PATHS = {"xla-while": "sparse-device",
                 "xla-while-segments": "sparse-device-segments",
                 "host-rounds": "sparse-host-rounds"}


class SGD:
    """Ref: Optimizer/SGD — optimize(initModel, trainData) → fitted coeffs."""

    def __init__(self, params: SGDParams):
        self.params = params

    def optimize_csr(self, loss_func: LossFunc, init_coeffs: np.ndarray,
                     features_csr, labels: np.ndarray,
                     weights: Optional[np.ndarray] = None,
                     mesh: Optional[Mesh] = None,
                     config=None, listeners=(),
                     tag: Optional[str] = None):
        """Host CSR fallback for wide sparse input (HashingTF at 2^18 dims
        would need terabytes dense — ref trains SparseVector natively,
        OnlineLogisticRegression.java:364-388 / BLAS.java:78).

        Mirrors ``_sgd_round_math`` exactly — the same contiguous-chunk
        sharding as ``shard_batch`` (p padded shards of length ⌈n/p⌉), the
        same per-task batch share/clip/wrap (SGD.java:206-213,262-284) and
        the same update/termination — so sparse and dense fits agree on
        small dims (parity-tested). Math in float64 on host; gradients via
        scipy's CSR matvec kernels.

        ``config``/``listeners`` run the rounds through ``iterate_bounded``
        with an un-jitted host body (jit_round=False): the sparse fit
        checkpoints/resumes mid-iteration exactly like the dense path — the
        reference's state persistence is representation-agnostic
        (SGD.java:308-360) and so is ours.
        """
        # the mesh fixes the simulated task count p: a PURE function of the
        # mesh configuration, never of process state — sparse and dense
        # fits must slice batches identically (the parity contract below)
        # and a checkpointed carry must resume under the same p
        mesh = mesh or default_mesh()
        with tracer.span("sgd.optimize", path="csr-host",
                         rounds=self.params.max_iter,
                         shards=data_shard_count(mesh)):
            return self._optimize_csr(loss_func, init_coeffs, features_csr,
                                      labels, weights, mesh, config,
                                      listeners, tag)

    def _optimize_csr(self, loss_func, init_coeffs, features_csr, labels,
                      weights, mesh, config, listeners, tag):
        prm = self.params
        p = data_shard_count(mesh)
        n, d = features_csr.shape
        ls = -(-n // p) if n else 1  # padded local length (shard_batch)
        lb_base, lb_rem = prm.global_batch_size // p, \
            prm.global_batch_size % p
        y = np.asarray(labels, np.float64)
        w = (np.ones(n, np.float64) if weights is None
             else np.asarray(weights, np.float64))
        X = features_csr.tocsr()

        _check_method(prm)
        rule = _update_rule(prm, xp=np)

        def round_body(carry, epoch):
            coeffs, offsets, _, opt = carry
            offsets = offsets.copy()  # carry is functional (checkpointable)
            row_parts = []
            for s in range(p):
                lb = min(lb_base + (1 if s < lb_rem else 0), ls)
                rel = np.arange(lb)
                idx = offsets[s] + rel
                gidx = s * ls + idx[idx < ls]  # clip at shard end
                row_parts.append(gidx[gidx < n])  # padding rows weigh 0
                offsets[s] = 0 if offsets[s] + lb >= ls else offsets[s] + lb
            rows = np.concatenate(row_parts)
            Xb, yb, wb = X[rows], y[rows], w[rows]
            dots = Xb @ coeffs
            loss_sum, multipliers = loss_func.terms(dots, yb, wb, xp=np)
            loss_sum = float(loss_sum)
            grad = Xb.T @ np.asarray(multipliers, np.float64)
            total_w = float(wb.sum())
            if total_w > 0:
                updated, opt = rule(grad, np.float64(total_w), coeffs,
                                    opt)
                updated, _ = regularize(updated, prm.reg, prm.elastic_net,
                                        prm.learning_rate, xp=np)
                coeffs = np.asarray(updated, np.float64)
            mean_loss = loss_sum / max(total_w, 1e-30)
            return coeffs, offsets, np.float64(mean_loss), opt

        from flink_ml_tpu.iteration.iteration import iterate_bounded

        algo = _health_tag(loss_func, tag)
        health_on = _health.armed()
        if health_on:
            # host rounds: convergence telemetry rides a listener at the
            # epoch boundary — the carry is already host float64 here
            listeners = tuple(listeners) + (
                _health.ConvergenceListener.for_params(algo, init_coeffs),)

        opt0 = tuple(np.zeros(d, np.float64)
                     for _ in range(_OPT_VECTORS[prm.method]))
        if prm.method == "adam":
            opt0 = opt0 + (np.float64(0.0),)
        init = (np.asarray(init_coeffs, np.float64).copy(),
                np.zeros(p, np.int64), np.float64(np.inf), opt0)
        # host rounds, one ``epoch`` span each: nothing is enqueued and
        # nothing awaited, the names are the dense paths' for one tree
        with tracer.span("sgd.launch"):
            coeffs, _, mean_loss, _ = iterate_bounded(
                init, round_body, max_iter=prm.max_iter,
                terminate=lambda carry, epoch: carry[2] < prm.tol,
                config=config, listeners=listeners, jit_round=False)
        self.last_execution_path = "csr-host"
        with tracer.span("sgd.fetch"):
            mean_loss = float(mean_loss)
        with tracer.span("sgd.health"):
            if not health_on:
                _health.guard_final_state(algo, coeffs, loss=mean_loss)
        return coeffs, mean_loss

    def optimize_sparse(self, loss_func: LossFunc, init_coeffs: np.ndarray,
                        column, labels, weights=None,
                        mesh: Optional[Mesh] = None, config=None,
                        listeners=(), tag: Optional[str] = None):
        """A fit over a ``DeviceSparseColumn`` (``linalg/sparse.py``) where
        it lies: :meth:`optimize`'s schedule, programs, carries, paths and
        spans, one program a plain fit started on the device, with the
        sparse window's gather and scatter in each round in place of the
        dense products (``ops/sparse_window.py``). Returns (coeffs
        ``(size,)`` np.ndarray, final mean loss float).

        ``sgd.optimize`` carries ``form``, the gradient's form
        (``sparse_window.form``), ``narrow``, the entry positions the
        dictionary form takes, and ``path``: ``sparse-device``,
        ``sparse-device-segments`` or ``sparse-host-rounds``;
        ``batch_reads``, the HBM reads of a round's batch the fit made
        (``ml.sgd batchReads``'s part); ``entries``, the window entries
        its rounds held (``sparseEntries``'), and ``dict_entries``, those
        of them the dictionary form took (``dictEntries``'). A recording
        ``sgd.launch`` of the compiled paths carries ``gradient_ops``, the
        names of the operations the program runs for the gradient
        (``sparse_window.gradient_ops``). A data mesh only: under a model
        axis the coefficients would be split, and a gather over them is
        not."""
        mesh = mesh or default_mesh()
        if model_axis_of(mesh) is not None:
            raise ValueError("a sparse device fit runs over a data mesh, "
                             "not one with a model axis")
        batch = _batch_form(self.params, mesh, len(column), column.entries,
                            arrays=2)
        with tracer.span("sgd.optimize", rounds=self.params.max_iter,
                         shards=data_shard_count(mesh),
                         weights="unit" if weights is None else "column",
                         batch=batch,
                         form=sparse_window.form(column.hot, column.narrow),
                         narrow=len(column.narrow)) as sp:
            out = self._optimize(
                loss_func, init_coeffs, column, labels, weights, mesh,
                jnp.float32, config, listeners, tag, batch,
                sparse=sparse_window.Layout(column.size, column.hot,
                                            column.narrow))
            sp.set_attribute("path", self.last_execution_path)
            sp.set_attribute("batch_reads", self.last_batch_reads)
            sp.set_attribute("entries", self.last_entries[0])
            sp.set_attribute("dict_entries", self.last_entries[1])
            return out

    def optimize(self, loss_func: LossFunc, init_coeffs: np.ndarray,
                 features: np.ndarray, labels: np.ndarray,
                 weights: Optional[np.ndarray] = None,
                 mesh: Optional[Mesh] = None,
                 dtype=jnp.float32,
                 config=None, listeners=(),
                 tag: Optional[str] = None):
        """Returns (coeffs (d,) np.ndarray, final mean loss float).

        With ``config``/``listeners`` (an ``IterationConfig`` needing host
        hooks — checkpointing, per-round callbacks), training runs as host-
        driven rounds through ``iterate_bounded``: resumable mid-fit from a
        checkpoint with results identical to the all-device program (the
        fault-injection bar of BoundedAllRoundCheckpointITCase).

        ``tag`` labels this fit's model-health telemetry (the estimator
        class name from models/common.py); with telemetry armed
        (observability/health.py) the compiled programs return per-epoch
        convergence rows + a non-finite sentinel, and every path raises
        the terminal ``NonFiniteState`` on a NaN/Inf state instead of
        returning garbage coefficients.

        One ``sgd.optimize`` span a fit, with a child at each boundary
        the host crosses (docs/observability.md, span catalogue):
        ``sgd.place_inputs``, ``sgd.init_carry``, ``sgd.build_program``,
        ``sgd.launch`` (the enqueue, never a wait), ``sgd.fetch`` (the
        blocking device→host reads) and ``sgd.health``.

        A plain fit (no ``config``/``listeners`` hook, health off) starts
        on the device: ``init_coeffs``, cast and padded, is the one host
        operand its program takes beside the table, and the zero carry
        and the epoch bounds are made inside
        (``_build_sgd_segment_program(fresh=True)``); ``sgd.launch`` says
        ``start="fresh"``. A carry that crosses the host — checkpointed
        segments, a restore, host rounds — is placed by one
        ``jax.device_put`` under ``sgd.init_carry`` (``start="carry"``).
        The two answer bit for bit."""
        mesh = mesh or default_mesh()
        batch = _batch_form(self.params, mesh, *features.shape)
        with tracer.span("sgd.optimize", rounds=self.params.max_iter,
                         shards=data_shard_count(mesh),
                         weights="unit" if weights is None
                         else "column", batch=batch) as sp:
            out = self._optimize(loss_func, init_coeffs, features, labels,
                                 weights, mesh, dtype, config, listeners,
                                 tag, batch)
            sp.set_attribute("path", self.last_execution_path)
            return out

    @staticmethod
    def _fetch_result(coeffs, d: int, mean_loss, boundary=()):
        """The blocking read of the fitted state every dense path ends
        in, where the wait for the enqueued rounds falls: ONE wait,
        under which a plain fit's ``boundary`` leaves cross too (their
        host values come back third)."""
        from flink_ml_tpu.iteration.iteration import read_boundary

        with tracer.span("sgd.fetch"):
            *vals, coeffs, mean_loss = read_boundary(
                (*boundary, coeffs, mean_loss))
            return (np.asarray(coeffs, np.float64)[:d], float(mean_loss),
                    vals)

    def _optimize(self, loss_func, init_coeffs, features, labels, weights,
                  mesh, dtype, config, listeners, tag, batch, sparse=None):
        algo = _health_tag(loss_func, tag)
        health_on = _health.armed()
        n = features.shape[0]
        d = features.shape[1]

        def path(name):
            return name if sparse is None else _SPARSE_PATHS[name]

        axes = data_axes(mesh)
        p = data_shard_count(mesh)
        spec0 = data_pspec(mesh)
        init_coeffs = np.asarray(init_coeffs)
        tp = model_axis_of(mesh) is not None
        # cross-replica sharded update (update_sharding.py; DP meshes
        # only — a TP mesh already splits the feature dim): pad the
        # coefficient carry to the shard multiple so the gradient
        # reduce-scatter and the per-replica slices line up (padded
        # coords stay exactly zero: zero grad → soft-threshold(0) = 0)
        sharded = _upd.enabled() and not tp
        if sharded:
            pad = (-d) % p
            if pad:
                init_coeffs = np.pad(init_coeffs, (0, pad))
        from jax.sharding import NamedSharding
        with tracer.span("sgd.place_inputs"):
            if tp:
                # tensor parallelism: feature dim padded to the model-axis
                # size and sharded over it (padded coords stay exactly
                # zero: zero features → zero grad → soft-threshold(0) = 0)
                tp_size = int(mesh.shape[MODEL_AXIS])
                pad = (-d) % tp_size
                if pad:
                    init_coeffs = np.pad(init_coeffs, (0, pad))
                rem = (-n) % p
                x_sharding = NamedSharding(mesh, P(spec0, MODEL_AXIS))
                if isinstance(features, jax.Array):
                    # device-resident input: cast/pad/reshard on device —
                    # the same residency contract as the DP branch
                    if pad or rem or features.dtype != jnp.float32:
                        features = _tp_prepare_program(
                            rem, pad, x_sharding)(features)
                else:
                    features = np.asarray(features, np.float32)
                    if pad or rem:
                        features = np.pad(features, ((0, rem), (0, pad)))
                xs = jax.device_put(features, x_sharding)
            else:
                # device-resident features/labels (device datagen or a
                # previous device stage) stay on device end-to-end — no
                # host round-trip
                if sparse is not None:
                    xs = (ensure_on_mesh(mesh, features.ids, axes,
                                         jnp.int32)[0],
                          ensure_on_mesh(mesh, features.values, axes,
                                         jnp.float32)[0])
                    if sparse.narrow:
                        # made whole on the column's mesh: moved only
                        # where the fit runs on another
                        dicts = features.dicts
                        if not dicts.sharding.is_equivalent_to(
                                NamedSharding(mesh, P()), dicts.ndim):
                            dicts = replicate(mesh, dicts)
                        xs += (dicts,)
                else:
                    xs, _ = ensure_on_mesh(mesh, features, axes,
                                           jnp.float32)
            ys, _ = ensure_on_mesh(mesh, labels, axes, jnp.float32)
            # no weight column: none is built. The programs take no
            # weight operand and a row weighs 1 where its round's batch
            # holds it; rows padded on above weigh 0 by the row count
            weighted = weights is not None
            ws = n_valid = None
            if weighted:
                ws, _ = ensure_on_mesh(mesh, weights, axes, jnp.float32)
            elif n % p:
                n_valid = n
        # the entries a round's windows hold, and those the dictionary
        # form takes: a sparse fit's counts
        rows = (0 if sparse is None else
                p * _local_batch(self.params, p, -(-n // p)))
        entries = rows * (0 if sparse is None else features.entries)
        dict_entries = rows * (0 if sparse is None else len(sparse.narrow))
        from flink_ml_tpu.iteration.iteration import (
            device_checkpoint_segment, needs_host_loop, run_segmented)

        seg_k = device_checkpoint_segment(config, listeners)
        plain = not needs_host_loop(config, listeners)
        # a plain fit with health off starts on the device: its program
        # makes the zero carry and the bounds itself, and the coefficients
        # go in as the call's host operand
        fresh = plain and not health_on

        def record_state(w0, opt):
            # per-replica update-state accounting (benchmark provenance):
            # measured from the carry's real buffers — SGD's coefficients
            # all-gather back to replicated every round, so this honestly
            # reports full size even under the sharded update; the moment
            # vectors are the state that genuinely shrinks 1/N (their
            # slices never all-gather), recorded both folded into the algo
            # total and as a standalone ".moments" record so the multihost
            # bench can gate on the moment bytes alone
            opt_leaves = list(jax.tree_util.tree_leaves(opt))
            if opt_leaves:
                _upd.record_state_bytes(f"{algo}.moments", opt_leaves, p,
                                        sharded)
            _upd.record_state_bytes(algo, [w0] + opt_leaves, p, sharded)

        # a carry that crosses the host (checkpoints, restores, host
        # rounds) has leaves that must live on the full mesh (replicated
        # or model-sharded coeffs, per-task offsets, moment vectors
        # sharded 1/N under the sharded update) — both for the mapped
        # round/segment and so that checkpoint restore re-places leaves
        # onto the right shardings (a sharded-adam resume puts each
        # moment slice back on its owning replica). The opt tuple rides
        # at the END of the carry so a method="sgd" checkpoint keeps the
        # stateless-era leaf order. Host arrays, placed by ONE call: a
        # leaf made on the default device first costs a program and a
        # transfer of its own, once a device.
        with tracer.span("sgd.init_carry"):
            _check_method(self.params)
            w0 = np.asarray(init_coeffs, dtype)
            if not fresh:
                w_sharding = NamedSharding(mesh, P(MODEL_AXIS) if tp
                                           else P())
                row_sharding = NamedSharding(mesh, P(spec0))
                scalar = NamedSharding(mesh, P())
                moments = _OPT_VECTORS[self.params.method]
                step = self.params.method == "adam"
                init = jax.device_put(
                    (w0, np.zeros((p,), np.int32),
                     np.asarray(np.inf, dtype),
                     (np.zeros(w0.shape[0], dtype),) * moments
                     + (np.zeros((), dtype),) * step),
                    (w_sharding, row_sharding, scalar,
                     (row_sharding if sharded else w_sharding,) * moments
                     + (scalar,) * step))
                w0 = init[0]
                record_state(w0, init[3])

        if seg_k or plain:
            # the compiled fast path: a plain fit is one max_iter segment;
            # a checkpointed fit runs K-round segments with the carry
            # snapshotted between them (one loop either way)
            from flink_ml_tpu.iteration.iteration import (
                read_boundary, segment_fusion_enabled)
            fused = segment_fusion_enabled()
            with tracer.span("sgd.build_program"):
                seg_prog = _build_sgd_segment_program(type(loss_func), mesh,
                                                      self.params,
                                                      health=health_on,
                                                      sharded=sharded,
                                                      fused=fused,
                                                      weighted=weighted,
                                                      n_valid=n_valid,
                                                      fresh=fresh,
                                                      sparse=sparse)
                # health carry lives OUTSIDE the checkpointed carry so the
                # snapshot format is identical with telemetry on or off; a
                # restore simply resumes the series at its epoch (earlier
                # rows stay NaN and are sliced off by `first`)
                hstate = {
                    "hist": _health_hist_program(
                        self.params.max_iter,
                        NamedSharding(mesh, P()))() if health_on else None,
                    "fin": True, "first": None, "epoch": 0,
                }

            def launch_segment(carry, epoch0, limit):
                """Enqueue one segment, no wait: the new carry and the
                boundary's leaves, all still on the device."""
                coeffs, offsets, _, opt = carry
                if hstate["first"] is None:
                    hstate["first"] = int(epoch0)
                health_in = ((hstate["hist"], np.bool_(hstate["fin"]))
                             if health_on else ())
                operands = (xs, ys, ws, coeffs, offsets, opt,
                            np.int32(epoch0), np.int32(limit), *health_in)
                with tracer.span("sgd.launch", start="carry",
                                 batch=batch) as sp:
                    _note_gradient_ops(sp, seg_prog, sparse, *operands)
                    coeffs, offsets, opt, mean_loss, *tail = seg_prog(
                        *operands)
                # fused, the boundary is ONE stacked [epoch, stop(, fin)]
                # vector instead of a transfer a scalar
                if not health_on:
                    boundary = tuple(tail)
                elif fused:
                    boundary, hstate["hist"] = (tail[0],), tail[1]
                else:
                    epoch, stop, hstate["hist"], fin = tail
                    boundary = (epoch, stop, fin)
                return (coeffs, offsets, mean_loss, opt), boundary

            def crossed(vals):
                """A boundary's values, on the host: where the fit
                stands."""
                if fused:
                    vals, = vals
                epoch, stop = int(vals[0]), bool(vals[1])
                hstate["epoch"] = epoch
                if health_on:
                    # epoch-boundary health check: the boundary is this
                    # mode's host sync point, so reading the sentinel
                    # costs no extra round-trip (it rides the bundle) —
                    # and a NaN state fails the fit NOW instead of
                    # burning the remaining segments
                    hstate["fin"] = bool(vals[2])
                    if not hstate["fin"]:
                        _finish_fit_health(
                            algo, True, hstate["hist"], False, epoch,
                            None, None, epoch0=hstate["first"])
                return epoch, stop

            def run_segment(carry, epoch0, limit):
                carry, boundary = launch_segment(carry, epoch0, limit)
                with tracer.span("sgd.fetch"):
                    vals = read_boundary(boundary)
                return (carry, *crossed(vals))

            if seg_k:
                # the driver needs epoch and stop to go on: one read a
                # segment boundary, the fitted state's pair at the end
                coeffs, _, mean_loss, _ = run_segmented(
                    run_segment, init, self.params.max_iter, seg_k,
                    config.checkpoint_manager)
                out, mean_loss, _ = self._fetch_result(coeffs, d,
                                                       mean_loss)
            else:
                # a plain fit is one segment, and everything it ends in
                # crosses to the host under one wait
                if fresh:
                    # jit's own argument path places the coefficients:
                    # the one transfer a device this start costs
                    with tracer.span("sgd.launch", start="fresh",
                                     batch=batch) as sp:
                        _note_gradient_ops(sp, seg_prog, sparse, xs, ys, ws,
                                           w0)
                        coeffs, _, opt, mean_loss, *boundary = seg_prog(
                            xs, ys, ws, w0)
                    # the returned leaves' metadata: nothing is waited on
                    record_state(coeffs, opt)
                else:
                    (coeffs, _, mean_loss, _), boundary = launch_segment(
                        init, 0, self.params.max_iter)
                out, mean_loss, vals = self._fetch_result(
                    coeffs, d, mean_loss, boundary)
                crossed(vals)
            self.last_execution_path = path("xla-while-segments" if seg_k
                                            else "xla-while")
            done = hstate["epoch"] - (hstate["first"] or 0)
            self.last_batch_reads = _count_batch_reads(
                batch, done, entries, dict_entries)
            self.last_entries = (done * entries, done * dict_entries)
            with tracer.span("sgd.health"):
                _finish_fit_health(
                    algo, health_on, hstate["hist"], hstate["fin"],
                    hstate["epoch"], mean_loss, out,
                    epoch0=hstate["first"] or 0)
            return out, mean_loss

        from flink_ml_tpu.iteration.iteration import iterate_bounded

        with tracer.span("sgd.build_program"):
            round_fn = _build_sgd_round_program(
                type(loss_func), mesh, self.params, sharded=sharded,
                weighted=weighted, n_valid=n_valid, sparse=sparse)

        rounds = [0]

        def body(carry, epoch):
            coeffs, offsets, _, opt = carry
            coeffs, offsets, mean_loss, opt = round_fn(xs, ys, ws,
                                                       coeffs, offsets,
                                                       opt)
            rounds[0] += 1
            return coeffs, offsets, mean_loss, opt

        if health_on:
            # host-driven rounds: the health series rides an extra
            # listener instead of a program variant (the listeners are
            # what forced this mode); it reads lagged carries so the
            # loop's listener-vs-device overlap survives
            listeners = tuple(listeners) + (
                _health.ConvergenceListener.for_params(
                    algo, np.asarray(w0)),)

        # the rounds are enqueued (and their stop bits awaited) by the
        # iteration runtime, one ``epoch`` span each under this one
        with tracer.span("sgd.launch", batch=batch):
            final = iterate_bounded(
                init, body, max_iter=self.params.max_iter,
                terminate=lambda carry, epoch: carry[2] < self.params.tol,
                config=config, listeners=listeners, jit_round=False)
        coeffs, _, mean_loss, _ = final
        self.last_execution_path = path("host-rounds")
        self.last_batch_reads = _count_batch_reads(batch, rounds[0],
                                                   entries, dict_entries)
        self.last_entries = (rounds[0] * entries, rounds[0] * dict_entries)
        out, mean_loss, _ = self._fetch_result(coeffs, d, mean_loss)
        with tracer.span("sgd.health"):
            if not health_on:
                _health.guard_final_state(algo, out, loss=mean_loss)
        return out, mean_loss
