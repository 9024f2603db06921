"""Per-worker gradient substrate — "worker i computes grad f_i" (Alg. 1 l.5).

The paper's workers are realized as slices of the global batch: worker i
owns rows ``[i*B/W, (i+1)*B/W)``.  ``split_batch`` reshapes the batch to
a leading worker axis and ``per_worker_grads`` vmaps the loss gradient
over it, returning worker-stacked gradient leaves ``(W, *param.shape)``
whose mean over axis 0 equals the full-batch gradient exactly (each
worker's loss is the mean over its own rows, and all shards are equal
size).

On the production mesh the worker axis is sharded ``P(("pod","data"))``,
so the vmap body runs as W parallel per-device gradient computations and
the stacked leaves never materialize unsharded — the compressed
collectives in ``repro.dist.collectives`` consume them in place.  Under
the train step's ambient data-parallel mesh the map is a ``shard_map``
over the worker axes (``repro.comm.wire.on_workers``): each device
computes its own workers' gradients, and a Pallas codec encoding in
the backward pass (the fused-VJP path) needs no GSPMD partitioning.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from repro.comm.wire import on_workers

tmap = jax.tree_util.tree_map


def split_batch(batch, w: int):
    """Reshape every leaf's leading batch dim ``B`` to ``(W, B/W, ...)``.

    Rows are assigned contiguously, so worker i's shard is exactly
    ``leaf[i*B/W:(i+1)*B/W]`` — the reshape is a pure relabeling and
    round-trips losslessly.
    """

    def one(a):
        b = a.shape[0]
        if b % w:
            raise ValueError(
                f"batch dim {b} not divisible by {w} workers (leaf shape "
                f"{a.shape})"
            )
        return a.reshape(w, b // w, *a.shape[1:])

    return tmap(one, batch)


def per_worker_grads(
    loss_fn: Callable, params, wbatch
) -> Tuple[Any, jax.Array, Any]:
    """Stacked per-worker gradients of ``loss_fn(params, batch_i)``.

    ``loss_fn`` must return ``(loss, metrics)`` (has_aux convention, as
    ``repro.models.model.train_loss`` does).  Returns
    ``(wgrads, loss, metrics)`` where ``wgrads`` leaves are shaped
    ``(W, *param.shape)``, ``loss`` is the mean worker loss (== the
    full-batch loss for mean-reduced losses over equal shards), and
    ``metrics`` leaves are averaged over the worker axis.
    """

    def one(p, b):
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b)
        return g, loss, aux

    wgrads, losses, aux = on_workers(one, params, wbatch, in_axes=(None, 0))
    loss = jnp.mean(losses)
    metrics = tmap(lambda a: jnp.mean(a, axis=0), aux)
    return wgrads, loss, metrics
