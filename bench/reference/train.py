"""Plain reference of the first training steps of a cell.

Data-parallel DIANA with the blockwise int8 codec, then AdamW, written
out from the algorithm's equations (Mishchenko et al. 2019; the traffic
file states alpha, the codec's tile and the optimizer's settings):

    worker i:  g_i = grad of its rows' mean loss
               m_i = Q(g_i - h_i)                    (q8 codec, below)
    master:    g   = mean_i h_i + mean_i m_i
               h_i <- h_i + alpha m_i
               AdamW(g) with linear warm-up then cosine decay to 10%

The codec Q flattens a leaf to float32 rows of 128 lanes, pads the rows
to whole tiles of ``block_rows`` rows (fewer for a smaller leaf), and
stochastically rounds each element to an int8 multiple of its tile's
scale max|x| / 127, up with probability equal to the remainder.  Its
uniforms are drawn with the keys the system's round uses, so both sides
round alike: step key -> (next, round); round -> (message, aux, agg);
leaf j of the tree -> fold_in(message, j) -> (contractive, unbiased);
unbiased -> one key per worker; uniforms of the padded (rows, 128) block.

Parameters, shifts and decoded messages are stored in the leaf's own
dtype, as the configuration states; every computation is float32 at the
highest matmul precision.  Gradients are taken over blocks of rows and
summed, so the reference fits beside nothing else on one chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import model as RM

F32 = jnp.float32
LANE = 128
LEVELS = 127
SCALE_FLOOR = 1e-30
#: leaves whose reference gradient is under this share of the median
#: leaf's are left out of the parameter change
EXCLUDE_REL = 1e-3
#: rows per block of a worker's gradient, so the reference fits beside
#: nothing else on one chip
REFERENCE_ROWS = 1


def q8_roundtrip(x, key, block_rows: int):
    """Decoded q8 message of ``x`` (float32 result, ``x``'s shape)."""
    d = x.size
    rows = max(1, -(-d // LANE))
    block = min(block_rows, rows)
    rows_pad = -(-rows // block) * block
    flat = jnp.pad(jnp.ravel(x).astype(F32), (0, rows_pad * LANE - d))
    u = jax.random.uniform(key, (rows_pad, LANE)).reshape(-1, block * LANE)
    xb = flat.reshape(-1, block * LANE)
    scale = jnp.maximum(jnp.max(jnp.abs(xb), axis=1), SCALE_FLOOR) / LEVELS
    y = xb / scale[:, None]
    lo = jnp.floor(y)
    q = lo + (u < (y - lo)).astype(F32)
    return (q * scale[:, None]).reshape(-1)[:d].reshape(x.shape)


def lr_at(step, sched: dict):
    """Learning rate of optimizer step ``step`` (1-based)."""
    base, warm, total = sched["lr"], sched["warmup_steps"], sched["total_steps"]
    if step < warm:
        return base * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return base * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog)))


@jax.jit
def _adam(p, mom, vel, g, lr, bc1, bc2, b1, b2, eps, wd):
    mom = b1 * mom + (1 - b1) * g
    vel = b2 * vel + (1 - b2) * jnp.square(g)
    pf = p.astype(F32)
    delta = (mom / bc1) / (jnp.sqrt(vel / bc2) + eps) + wd * pf
    return (pf - lr * delta).astype(p.dtype), mom, vel


class Reference:
    """Drives the reference over the first steps of one cell.

    ``model`` is the configuration file's model description, ``traffic``
    the traffic file; ``precision`` is ``"float32"`` (the reference) or
    ``"fp8"`` (the control).  ``fault`` plants one of the faults a broken
    program could have, so their readings can be taken: ``"unchanged"``
    (the step returns its state), ``"half_batch"`` (half of each worker's
    rows left out), ``"no_exchange"`` (each worker keeps its own message;
    worker 0's state is read), ``"loss_altered"`` (the reported loss off
    by 1%).  Worker i computes on ``devices[i % len(devices)]``; leaf j's
    optimizer state and update live on ``devices[j % len(devices)]``.
    """

    def __init__(self, model: dict, traffic: dict, precision="float32",
                 fault=None, devices=None):
        self.m = model
        self.t = traffic
        self.ein = RM.Matmul(precision)
        self.fault = fault
        self.devices = list(devices or jax.devices()[:1])
        self._grad = jax.jit(jax.value_and_grad(self._rows_loss_sum))
        block = traffic["q8_block_rows"]
        alpha = traffic["shift_alpha"]

        def msg(g, h, key):
            m = q8_roundtrip(g - h.astype(F32), key, block).astype(h.dtype)
            hf, mf = h.astype(F32), m.astype(F32)
            return (hf + alpha * mf).astype(h.dtype), hf + mf
        self._msg = jax.jit(msg)

    def _rows_loss_sum(self, params, tokens):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(RM.row_losses(params, tokens, self.m, self.ein))

    def worker_grad(self, params, rows):
        """Mean loss and float32 gradient over ``rows`` (R, S), taken in
        blocks of ``REFERENCE_ROWS`` rows."""
        rb = REFERENCE_ROWS
        if self.fault == "half_batch":
            if rows.shape[0] < 2:
                raise ValueError("half_batch needs two rows per worker")
            rows = rows[: rows.shape[0] // 2]
        n = rows.shape[0]
        tot, grad = 0.0, None
        for lo in range(0, n, rb):
            l, g = self._grad(params, rows[lo: lo + rb])
            tot = tot + l
            grad = g if grad is None else jax.tree_util.tree_map(
                jnp.add, grad, g)
        return tot / n, [a / n for a in jax.tree_util.tree_leaves(grad)]

    def run(self, params0, batches, key, n_steps: int):
        """Reference readings of ``n_steps`` steps from ``params0`` on
        ``batches`` (a list of (B, S) token arrays) with the state key
        ``key``.  Returns ``{"loss": [..], "grad_norm": {leaf: ..},
        "change_norm": {leaf: ..}}``; leaves are named by their path."""
        t = self.t
        w = t["workers"]
        opt = t["optimizer"]
        hyper = (opt["beta1"], opt["beta2"], opt["eps"], opt["weight_decay"])
        b1 = hyper[0]
        nd = len(self.devices)
        devs = [self.devices[i % nd] for i in range(w)]
        leaves0, treedef = jax.tree_util.tree_flatten(params0)
        home = [self.devices[j % nd] for j in range(len(leaves0))]
        leaves0 = [jax.device_put(p, d) for p, d in zip(leaves0, home)]
        names = leaf_names(params0)
        params = list(leaves0)
        h = [[jax.device_put(jnp.zeros_like(p), devs[i]) for p in params]
             for i in range(w)]
        mom = [jax.device_put(jnp.zeros(p.shape, F32), d)
               for p, d in zip(params, home)]
        vel = [jax.device_put(jnp.zeros(p.shape, F32), d)
               for p, d in zip(params, home)]
        out = {"loss": []}
        for k in range(n_steps):
            tokens = np.asarray(batches[k])
            rows = tokens.reshape(w, tokens.shape[0] // w, tokens.shape[1])
            key, sub = jax.random.split(key)
            k_msg = jax.random.split(sub, 3)[0]
            # g = mean_i (h_i + m_i), accumulated beside leaf j's state
            acc = [jax.device_put(jnp.zeros(p.shape, F32), d)
                   for p, d in zip(params, home)]
            worker_loss = []
            for i in range(w):
                tree = jax.device_put(
                    jax.tree_util.tree_unflatten(treedef, params), devs[i])
                li, g = self.worker_grad(tree, jax.device_put(rows[i],
                                                              devs[i]))
                del tree
                worker_loss.append(li)
                for j in range(len(params)):
                    lk = jax.random.split(jax.random.fold_in(k_msg, j))[1]
                    wkey = jax.device_put(jax.random.split(lk, w)[i], devs[i])
                    h[i][j], contrib = self._msg(g[j], h[i][j], wkey)
                    g[j] = None
                    if self.fault == "no_exchange":
                        contrib = contrib * (w if i == 0 else 0)
                    acc[j] = acc[j] + jax.device_put(contrib, home[j])
                    del contrib
            step = k + 1
            lr = lr_at(step, t["schedule"])
            bc1, bc2 = 1 - b1 ** step, 1 - hyper[1] ** step
            for j, p in enumerate(params):
                new_p, mom[j], vel[j] = _adam(p, mom[j], vel[j], acc[j] / w,
                                              lr, bc1, bc2, *hyper)
                acc[j] = None
                if self.fault != "unchanged":
                    params[j] = new_p
            if k == 0:
                out["grad_norm"] = {
                    n: float(jnp.linalg.norm(mm)) / (1 - b1)
                    for n, mm in zip(names, mom)}
            loss = sum(float(x) for x in worker_loss) / w
            if self.fault == "loss_altered":
                loss = loss * 1.01
            out["loss"].append(float(loss))
        out["change_norm"] = {
            n: float(jnp.linalg.norm(p.astype(F32) - p0.astype(F32)))
            for n, p, p0 in zip(names, params, leaves0)}
        return out


def leaf_names(tree):
    """Path names of a tree's leaves, in flattening order."""
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_leaves_with_path(tree)]


def gaps(prog: dict, ref: dict):
    """The compared numbers: the largest relative gap of the per-step
    losses, and of the per-leaf norms of the first gradient and of the
    three steps' parameter change.  A norm gap is measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger.  Leaves whose reference gradient is under ``EXCLUDE_REL`` of
    the median leaf's move by round-off alone and are left out of the
    change."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))

    def worst(p, r, keep):
        med = float(np.median([r[n] for n in keep]))
        return max(abs(p[n] - r[n]) / max(r[n], med) for n in keep)

    g_ref = ref["grad_norm"]
    names = list(g_ref)
    g_med = float(np.median(list(g_ref.values())))
    moving = [n for n in names if g_ref[n] >= EXCLUDE_REL * g_med]
    return {
        "loss_gap": loss,
        "grad_gap": worst(prog["grad_norm"], g_ref, names),
        "change_gap": worst(prog["change_norm"], ref["change_norm"], moving),
    }
