"""Pipeline parallelism: GPipe-style microbatch rotation over the
``pipeline`` mesh axis.

Capability extension beyond the reference (SURVEY.md §5.8).  TPU-first
design: the schedule is a statically-bounded loop inside ``shard_map`` —
each device owns ONE stage, activations hop to the next stage with
``lax.ppermute`` (a neighbor exchange riding ICI), and the loop runs
``n_micro + n_stages - 1`` ticks so every stage is busy once the pipeline
fills.  Reverse-mode AD differentiates straight through the loop and the
ppermutes (the transpose of a ppermute is the reverse ppermute), so one
``jax.grad`` over the pipeline is pipeline-parallel backprop.

Two schedules:

- ``pipeline_apply``: homogeneous stages (identical stage_fn + stacked
  params + shape-preserving activations).  Params are sharded one stage
  per device; the fast path for transformer-style towers.
- ``pipeline_apply_hetero``: arbitrary per-stage functions and activation
  shapes (stem / downsampling / head — i.e. real models like ResNet).
  Each tick dispatches through ``lax.switch`` on the stage index, so a
  device executes only ITS stage's code; activations cross stage
  boundaries flattened into one max-size rotating buffer (padding costs
  some ICI bytes, shapes stay static).  ``split_sequential`` cuts a built
  ``nn.Sequential`` into flop-balanced stages for it.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu.parallel.mesh import PIPELINE_AXIS


def pipeline_apply_local(stage_fn: Callable, stage_params, x_micro, *,
                         axis: str = PIPELINE_AXIS):
    """Per-device body (run inside shard_map over ``axis``).

    stage_params: THIS stage's params (leading pipeline dim stripped).
    x_micro: (M, mb, ...) microbatched input, replicated over the axis.
    Returns (M, mb, ...) outputs, replicated (psum-broadcast from the
    last stage)."""
    stage = lax.axis_index(axis)
    n = lax.psum(1, axis)  # static: mesh axis size
    m = x_micro.shape[0]
    total = m + n - 1
    perm = [(i, (i + 1) % n) for i in range(n)]

    y_shape = jax.eval_shape(stage_fn, stage_params, x_micro[0])
    assert y_shape.shape == x_micro.shape[1:], (
        "pipeline stages must preserve activation shape "
        f"(got {y_shape.shape} vs {x_micro.shape[1:]})")

    def tick(t, state):
        buf, outs = state
        mb_idx = jnp.clip(t, 0, m - 1)
        # stage 0 injects a fresh microbatch; others consume the rotated buf
        inp = jnp.where(stage == 0, x_micro[mb_idx], buf)
        y = stage_fn(stage_params, inp)
        out_idx = t - (n - 1)  # microbatch leaving the last stage this tick
        write = jnp.logical_and(stage == n - 1, out_idx >= 0)
        updated = lax.dynamic_update_index_in_dim(
            outs, y, jnp.clip(out_idx, 0, m - 1), 0)
        outs = jnp.where(write, updated, outs)
        buf = lax.ppermute(y, axis, perm)
        return buf, outs

    # inits must be marked varying over the shard_map axis (plain zeros
    # would be replicated and fail the loop-carry type check); adding a
    # zeroed axis_index does that without an extra stage_fn evaluation
    vary0 = (lax.axis_index(axis) * 0).astype(y_shape.dtype)
    buf0 = jnp.zeros(y_shape.shape, y_shape.dtype) + vary0
    outs0 = jnp.zeros((m,) + y_shape.shape, y_shape.dtype) + vary0
    # static bounds -> scan lowering: rolled body, differentiable
    _, outs = lax.fori_loop(0, total, tick, (buf0, outs0))
    # only the last stage holds real outputs; psum broadcasts them (all
    # other stages contribute zeros)
    return lax.psum(jnp.where(stage == n - 1, outs, 0.0), axis)


def pipeline_apply(stage_fn: Callable, stacked_params, x, mesh: Mesh, *,
                   n_microbatches: int, axis: str = PIPELINE_AXIS):
    """Global-view GPipe: ``stacked_params`` has a leading stage dim of
    size mesh.shape[axis] (stage i's params at index i); ``x`` is
    (batch, ...).  The batch is split into ``n_microbatches`` and pushed
    through the stages; returns (batch, ...) outputs.

    stage_fn(params_i, x_mb) -> y_mb must preserve shape."""
    b = x.shape[0]
    assert b % n_microbatches == 0, "batch must divide into microbatches"
    x_micro = x.reshape((n_microbatches, b // n_microbatches) + x.shape[1:])

    p_spec = jax.tree_util.tree_map(
        lambda leaf: P(axis, *([None] * (leaf.ndim - 1))), stacked_params)
    fn = shard_map(
        partial(_pipeline_body, stage_fn, axis),
        mesh=mesh,
        in_specs=(p_spec, P()),
        out_specs=P(),
    )
    y_micro = fn(stacked_params, x_micro)
    return y_micro.reshape((b,) + y_micro.shape[2:])


def _pipeline_body(stage_fn, axis, stacked_params, x_micro):
    # strip the leading (size-1 after sharding) stage dim from each leaf
    local = jax.tree_util.tree_map(lambda a: a[0], stacked_params)
    return pipeline_apply_local(stage_fn, local, x_micro, axis=axis)


# --------------------------------------------------------------------- #
# heterogeneous-stage GPipe                                             #
# --------------------------------------------------------------------- #
def pipeline_apply_hetero(stage_fns, stage_params, x, mesh: Mesh, *,
                          n_microbatches: int, axis: str = PIPELINE_AXIS):
    """GPipe over stages with DIFFERENT functions and activation shapes.

    stage_fns: list of n callables, ``f_j(params_j, x_j) -> y_j``; the
    boundary shapes are inferred with ``jax.eval_shape`` by chaining.
    stage_params: list of n per-stage pytrees (heterogeneous trees cannot
    be stacked, so they ride into shard_map replicated; the pipelined
    resource is compute + activation memory — use ``pipeline_apply`` when
    stages are homogeneous and params can be sharded too).
    x: (batch, ...) input to stage 0.  Returns (batch, ...) outputs of the
    last stage.

    Differentiation: GPipe's backward is itself a pipeline run in reverse,
    and it is implemented exactly that way via ``jax.custom_vjp`` — the
    forward stashes each device's per-tick input buffer, the backward
    walks ticks in reverse recomputing each stage locally (standard GPipe
    rematerialization) and ppermuting input-cotangents to the previous
    stage.  (``lax.switch`` appears only in primal computations, where it
    keeps each device executing ONLY its stage's code; its transpose is
    never taken.)
    """
    n = mesh.shape[axis]
    assert len(stage_fns) == n and len(stage_params) == n, \
        f"{len(stage_fns)} stages for a {n}-device '{axis}' axis"
    b = x.shape[0]
    assert b % n_microbatches == 0, "batch must divide into microbatches"
    mb = b // n_microbatches
    m = n_microbatches
    total = m + n - 1
    x_micro0 = x.reshape((m, mb) + x.shape[1:])
    in_shape = x_micro0.shape[1:]

    # chain eval_shape to find every boundary's activation shape
    shapes = [jax.eval_shape(lambda xx: xx, x_micro0[0])]
    for f, p in zip(stage_fns, stage_params):
        shapes.append(jax.eval_shape(f, p, shapes[-1]))
    dtypes = {s.dtype for s in shapes}
    assert len(dtypes) == 1, f"stage boundaries must share a dtype: {dtypes}"
    dtype = shapes[0].dtype
    sizes = [max(1, int(np.prod(s.shape))) for s in shapes]
    dbuf = max(sizes)  # one rotating-buffer size fits any boundary
    out_shape = shapes[n].shape

    from jax.flatten_util import ravel_pytree
    unravels, p_sizes = [], []
    for p in stage_params:
        fl, un = ravel_pytree(p)
        unravels.append(un)
        p_sizes.append(int(fl.size))
    pbuf = max(1, max(p_sizes))

    fwd_perm = [(i, (i + 1) % n) for i in range(n)]
    rev_perm = [(i, (i - 1) % n) for i in range(n)]

    def _make_fwd_body(with_res: bool):
        def fwd_body(params_tuple, x_micro):
            stage = lax.axis_index(axis)

            def make_branch(j):
                def branch(operands):
                    buf, xmb = operands
                    inp = (xmb if j == 0
                           else buf[:sizes[j]].reshape(shapes[j].shape))
                    y = stage_fns[j](params_tuple[j], inp)
                    return jnp.pad(y.reshape(-1), (0, dbuf - sizes[j + 1]))
                return branch

            branches = [make_branch(j) for j in range(n)]

            def tick(t, state):
                buf, outs, res = state
                if with_res:
                    # stash this tick's input buffer: the backward
                    # recomputes the stage from it (GPipe remat)
                    res = lax.dynamic_update_index_in_dim(res, buf, t, 0)
                mb_idx = jnp.clip(t, 0, m - 1)
                y_flat = lax.switch(stage, branches, (buf, x_micro[mb_idx]))
                out_idx = t - (n - 1)
                write = jnp.logical_and(stage == n - 1, out_idx >= 0)
                y_out = y_flat[:sizes[n]].reshape(out_shape)
                updated = lax.dynamic_update_index_in_dim(
                    outs, y_out, jnp.clip(out_idx, 0, m - 1), 0)
                outs = jnp.where(write, updated, outs)
                buf = lax.ppermute(y_flat, axis, fwd_perm)
                return buf, outs, res

            buf0 = jnp.zeros((dbuf,), dtype)
            outs0 = jnp.zeros((m,) + out_shape, dtype)
            res0 = jnp.zeros((total, dbuf) if with_res else (1, 1), dtype)
            _, outs, res = lax.fori_loop(0, total, tick, (buf0, outs0, res0))
            y = lax.psum(jnp.where(stage == n - 1, outs, 0.0), axis)
            return (y, res[None]) if with_res else y
        return fwd_body

    def bwd_body(params_tuple, x_micro, myres, dy_micro):
        stage = lax.axis_index(axis)
        res = myres[0]  # (total, dbuf)

        def make_branch(j):
            def branch(operands):
                dy_full, inp_flat, xmb = operands
                inp = (xmb if j == 0
                       else inp_flat[:sizes[j]].reshape(shapes[j].shape))
                dyj = dy_full[:sizes[j + 1]].reshape(shapes[j + 1].shape)
                _, pull = jax.vjp(stage_fns[j], params_tuple[j], inp)
                dp, dinp = pull(dyj)
                dp_fl = ravel_pytree(dp)[0].astype(dtype)
                dp_fl = jnp.pad(dp_fl, (0, pbuf - p_sizes[j]))
                dinp_fl = jnp.pad(dinp.reshape(-1), (0, dbuf - sizes[j]))
                return dp_fl, dinp_fl
            return branch

        branches = [make_branch(j) for j in range(n)]

        def tick(k, state):
            dcarry, dp_acc, dxs = state
            s = total - 1 - k  # walk ticks in reverse
            mb_idx = jnp.clip(s, 0, m - 1)
            # my output cotangent at tick s: the next stage's input
            # cotangent from tick s+1 (arrived via reverse ppermute), or —
            # for the last stage — the loss cotangent of the microbatch
            # that left the pipe at tick s
            out_idx = jnp.clip(s - (n - 1), 0, m - 1)
            dout_term = jnp.pad(dy_micro[out_idx].reshape(-1),
                                (0, dbuf - sizes[n]))
            dy_mine = jnp.where(stage == n - 1, dout_term, dcarry)
            dp_fl, dinp_fl = lax.switch(
                stage, branches, (dy_mine, res[s], x_micro[mb_idx]))
            active = jnp.logical_and(s - stage >= 0, s - stage < m)
            dp_fl = jnp.where(active, dp_fl, 0.0)
            dinp_fl = jnp.where(active, dinp_fl, 0.0)
            dp_acc = dp_acc + dp_fl
            # stage 0's input cotangent is dx for microbatch s
            upd = lax.dynamic_update_index_in_dim(
                dxs, dinp_fl[:sizes[0]].reshape(in_shape), mb_idx, 0)
            dxs = jnp.where(jnp.logical_and(stage == 0, active), upd, dxs)
            dcarry = lax.ppermute(dinp_fl, axis, rev_perm)
            return dcarry, dp_acc, dxs

        dcarry0 = jnp.zeros((dbuf,), dtype)
        dp0 = jnp.zeros((pbuf,), dtype)
        dxs0 = jnp.zeros((m,) + in_shape, dtype)
        _, dp_acc, dxs = lax.fori_loop(0, total, tick,
                                       (dcarry0, dp0, dxs0))
        dx = lax.psum(jnp.where(stage == 0, dxs, 0.0), axis)
        return dp_acc[None], dx

    p_specs = tuple(jax.tree_util.tree_map(lambda _: P(), p)
                    for p in stage_params)
    res_spec = P(axis, None, None)

    @jax.custom_vjp
    def pipe(params_tuple, x_micro):
        # inference path: no rematerialization stash
        return shard_map(_make_fwd_body(False), mesh=mesh,
                         in_specs=(p_specs, P()), out_specs=P(),
                         check_vma=False)(params_tuple, x_micro)

    def pipe_fwd(params_tuple, x_micro):
        y, res = shard_map(_make_fwd_body(True), mesh=mesh,
                           in_specs=(p_specs, P()),
                           out_specs=(P(), res_spec),
                           check_vma=False)(params_tuple, x_micro)
        return y, (params_tuple, x_micro, res)

    def pipe_bwd(saved, dy_micro):
        params_tuple, x_micro, res = saved
        dp_stack, dx = shard_map(
            bwd_body, mesh=mesh,
            in_specs=(p_specs, P(), res_spec, P()),
            out_specs=(P(axis, None), P()),
            check_vma=False,
        )(params_tuple, x_micro, res, dy_micro.astype(dtype))
        dparams = tuple(
            unravels[j](dp_stack[j, :p_sizes[j]]) for j in range(n))
        return dparams, dx

    pipe.defvjp(pipe_fwd, pipe_bwd)
    y_micro = pipe(tuple(stage_params), x_micro0)
    return y_micro.reshape((b,) + y_micro.shape[2:])


def split_sequential(model, n_stages: int, x, *, by: str = "flops",
                     training: bool = False):
    """Cut a built ``nn.Sequential`` into ``n_stages`` contiguous stages
    balanced by compiled forward flops (via utils.profiling) or by
    parameter count, for ``pipeline_apply_hetero``.

    Returns (stage_fns, stage_params): stage j applies the j-th group of
    children with the model's buffers frozen (GPipe microbatching changes
    batch-stat semantics anyway; train BN before or after splitting).
    """
    from bigdl_tpu.nn.containers import Sequential

    assert isinstance(model, Sequential), "split_sequential wants Sequential"
    model._built()
    children = list(model.modules)
    n_children = len(children)
    assert n_stages <= n_children, "more stages than layers"

    if by == "flops":
        from bigdl_tpu.utils import profiling
        rows = profiling.profile_layers(model, x, training=training,
                                        include_train=False)
        cost_by_module = {id(r["module"]): max(r["flops_fwd"], 1.0)
                          for r in rows}

        def child_cost(c):
            if getattr(c, "modules", None):
                return sum(cost_by_module.get(id(leaf), 1.0)
                           for leaf in _leaves_of(c))
            return cost_by_module.get(id(c), 1.0)
        costs = [child_cost(c) for c in children]
    else:
        costs = [sum(np.size(l) for l in
                     jax.tree_util.tree_leaves(model.params[str(i)])) + 1.0
                 for i in range(n_children)]

    # greedy contiguous partition: cut when a stage reaches total/n, or
    # when exactly enough children remain to fill the remaining stages
    # (otherwise a cost-heavy tail would starve them)
    total = sum(costs)
    target = total / n_stages
    bounds, acc, start = [], 0.0, 0
    for i, c in enumerate(costs):
        acc += c
        remaining_stages = n_stages - len(bounds) - 1
        children_left_after = n_children - (i + 1)
        if remaining_stages > 0 and children_left_after >= remaining_stages \
                and (acc >= target or children_left_after == remaining_stages):
            bounds.append((start, i + 1))
            start, acc = i + 1, 0.0
    bounds.append((start, n_children))
    assert len(bounds) == n_stages

    stage_fns, stage_params = [], []
    for a, bnd in bounds:
        group = children[a:bnd]
        g_params = {str(k): model.params[str(a + k)]
                    for k in range(len(group))}
        g_buffers = {str(k): (model.buffers or {}).get(str(a + k), {})
                     for k in range(len(group))}

        def make_fn(group=group, g_buffers=g_buffers):
            def fn(p, xx):
                for k, child in enumerate(group):
                    xx, _ = child.apply(p.get(str(k), {}), xx,
                                        buffers=g_buffers.get(str(k), {}),
                                        training=False)
                return xx
            return fn

        stage_fns.append(make_fn())
        stage_params.append(g_params)
    return stage_fns, stage_params


def _leaves_of(container):
    out = []
    for c in container.modules:
        if getattr(c, "modules", None):
            out.extend(_leaves_of(c))
        else:
            out.append(c)
    return out
