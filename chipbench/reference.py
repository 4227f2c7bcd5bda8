"""Plain reference of one ColRel round and the comparison that decides
``correct``.

A round, written from the paper (arXiv:2205.10998, Algs. 1 and 2) and
apart from the system's ``fl/``, ``core/`` and ``kernels/``:

* every slot of the client dimension runs T local SGD steps (gradient
  plus weight decay) from the broadcast model, one client at a time;
* the PS increment is ``sum_o c_o * delta_o`` with
  ``c = w * (tau * a)^T (a a^T * A)``: the tau-masked relay through the
  OPT-alpha weights A, restricted to the active cohort ``a``, with the
  blind weight ``w = 1 / n_active``;
* the PS adds the increment (plain SGD at rate 1).

The uplink mask tau of each round is drawn here from the round key chain
(``split`` then ``bernoulli(sub, p)``), the same protocol the system
documents.  The relay weights A, the uplink marginals p, the cohort mask
and the batches are the round's inputs as the host layer fed them.

``dtype=float32`` runs at ``HIGHEST`` matmul precision, on the device
that ``replay_device`` picks from the model's loss:

* the host's CPU where the loss runs a convolution, where float32 is
  exact.  ResNet-20 replays there: the TPU compiler takes about 19
  minutes over its ``HIGHEST`` convolutions, and the CPU replays its
  three rounds in seconds;
* JAX's first device otherwise, the cell's first chip once the trainer's
  state is freed: a model of matmuls at published widths (a transformer)
  needs minutes of the host's CPU for its replay, more than a run has.

The control runs the same code in bfloat16, on the chip.

Memory of the replay, D parameters of ``b`` bytes each: the parameters,
the round's increment (``_axpy`` donates its accumulator, the round's
update donates the old parameters) and one client's local step inside
``_client`` (its start, its running parameters, their gradient and the
update it returns), about ``5 * b * D`` bytes on the device plus one
step's activations.  ``model.loss`` is traced inside that one jitted
step, so a model module may compute its loss in blocks of rows
(``lax.map``, ``lax.scan``, ``jax.checkpoint``) to bound the
activations.  The host keeps float32 copies of the start and of each
round's parameters, ``4 * D`` bytes each.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr


def has_conv(jaxpr) -> bool:
    """Whether ``jaxpr`` (a ``Jaxpr`` or ``ClosedJaxpr``) runs a convolution,
    looking into every sub-jaxpr an equation carries (``pjit``,
    ``custom_jvp_call``, ``scan``, ``cond``'s branches, ...)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            return True
        for value in eqn.params.values():
            subs = value if isinstance(value, (tuple, list)) else (value,)
            if any(isinstance(sub, (Jaxpr, ClosedJaxpr)) and has_conv(sub) for sub in subs):
                return True
    return False


def replay_device(model, cfg, batch):
    """Where the float32 replay runs: the host's CPU where ``model.loss``
    runs a convolution on one minibatch of ``batch`` (leaves ``(n, T, b,
    ...)``), else JAX's first device.  Traced on shapes alone."""
    params = jax.eval_shape(functools.partial(model.init, cfg=cfg), jax.random.key(0))
    mb = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[2:], x.dtype), batch)
    conv = has_conv(jax.make_jaxpr(model.loss)(params, mb))
    return jax.devices("cpu")[0] if conv else jax.devices()[0]


def round_coefficients(A, tau, active):
    """Per-origin weights of the PS increment, in float64 on the host."""
    n = len(tau)
    a = np.ones(n) if active is None else np.asarray(active, np.float64)
    A = np.asarray(A, np.float64) * a[:, None] * a[None, :]
    w = 1.0 / max(a.sum(), 1.0)
    return w * (np.asarray(tau, np.float64) * a) @ A


@jax.jit
def _draw(key, p):
    key, sub = jax.random.split(key)
    return key, jax.random.bernoulli(sub, p)


def draw_taus(key, p_per_round):
    """The uplink masks of consecutive rounds from one key chain."""
    taus = []
    for p in p_per_round:
        key, tau = _draw(key, jnp.asarray(p, jnp.float32))
        taus.append(np.asarray(tau, np.float64))
    return taus


@functools.partial(jax.jit, static_argnames=("loss_fn", "steps"))
def _client(params, batches, lr, wd, *, loss_fn, steps):
    p = params
    first = None
    for t in range(steps):
        mb = jax.tree.map(lambda x: x[t], batches)
        value, g = jax.value_and_grad(loss_fn)(p, mb)
        if first is None:
            first = value
        p = jax.tree.map(lambda w, gw: w - lr * (gw + wd * w), p, g)
    delta = jax.tree.map(lambda a, b: a - b, p, params)
    return delta, first.astype(jnp.float32)


@functools.partial(jax.jit, donate_argnums=0)
def _axpy(acc, c, delta):
    return jax.tree.map(lambda s, d: s + c.astype(s.dtype) * d, acc, delta)


@functools.partial(jax.jit, donate_argnums=0)
def _apply(params, inc):
    return jax.tree.map(lambda w, d: w + d.astype(w.dtype), params, inc)


def run_rounds(model, params, rounds, *, lr, wd, dtype=jnp.float32, device=None):
    """Drive the plain round over ``rounds``, a list of dicts with the
    round's ``batch`` (leaves ``(n, T, b, ...)``, host arrays), ``tau``,
    ``A``, ``active``, on ``device`` (default: JAX's).  Returns, on the
    host in float32, ``{"p0"}``: the parameters it started from
    (``params`` in ``dtype``), ``{"after"}``: the parameters after each
    round, and per round ``{"losses"}``: the mean first-step loss and
    ``{"delta_norms"}``: the root mean square of the clients' local
    update norms, both over the active clients."""
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_device(device or jax.devices()[0]):
        return _run(model, params, rounds, lr, wd, dtype, precision)


@jax.jit
def _sq_norm(tree):
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))


def _run(model, params, rounds, lr, wd, dtype, precision):
    p = jax.tree.map(lambda x: jnp.asarray(x, dtype), params)
    start = jax.tree.map(lambda x: np.asarray(x, np.float32), p)
    after, losses, delta_norms = [], [], []
    with jax.default_matmul_precision(precision):
        for r in rounds:
            n = len(r["tau"])
            c = round_coefficients(r["A"], r["tau"], r["active"])
            a = np.ones(n) if r["active"] is None else np.asarray(r["active"], float)
            inc = jax.tree.map(jnp.zeros_like, p)
            loss_sum = sq_sum = 0.0
            for i in range(n):
                client_batch = jax.tree.map(
                    lambda x, i=i: jnp.asarray(x[i]) if x.dtype.kind in "iu"
                    else jnp.asarray(x[i], dtype),
                    r["batch"],
                )
                delta, first = _client(
                    p, client_batch, jnp.asarray(lr, dtype), jnp.asarray(wd, dtype),
                    loss_fn=model.loss, steps=_steps(r["batch"]),
                )
                if c[i] != 0.0:
                    inc = _axpy(inc, jnp.asarray(c[i], jnp.float32), delta)
                loss_sum += a[i] * float(first)
                sq_sum += a[i] * float(_sq_norm(delta))
            p = _apply(p, inc)
            after.append(jax.tree.map(lambda x: np.asarray(x, np.float32), p))
            losses.append(loss_sum / max(a.sum(), 1.0))
            delta_norms.append(np.sqrt(sq_sum / max(a.sum(), 1.0)))
    return {
        "p0": start,
        "after": after,
        "losses": np.asarray(losses),
        "delta_norms": np.asarray(delta_norms),
    }


def _steps(batch) -> int:
    return int(jax.tree.leaves(batch)[0].shape[1])


# ------------------------------------------------------------------ compare


def _leaf_norms(before, after):
    return [
        float(np.linalg.norm(np.asarray(b, np.float64) - np.asarray(a, np.float64)))
        for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after))
    ]


def leaf_gaps(prog_norms, ref_norms, keep):
    """Per kept leaf, |program norm - reference norm| as a share of the
    reference leaf's norm or the median leaf's, whichever is larger."""
    kept = [r for r, k in zip(ref_norms, keep) if k]
    med = float(np.median(kept))
    gaps = [abs(pn - rn) for pn, rn, k in zip(prog_norms, ref_norms, keep) if k]
    if med == 0.0:
        # nothing moved in the reference (no uplink of the round got
        # through): the program must not move either
        return [0.0 if g == 0.0 else float("inf") for g in gaps]
    return [g / max(rn, med) for g, rn in zip(gaps, kept)]


def _norm_gap(prog_norms, ref_norms, keep):
    """The gap of the whole update's norm, as a share of the reference's."""
    p = np.sqrt(sum(n * n for n, k in zip(prog_norms, keep) if k))
    r = np.sqrt(sum(n * n for n, k in zip(ref_norms, keep) if k))
    if r == 0.0:
        return 0.0 if p == 0.0 else float("inf")
    return float(abs(p - r) / r)


def compare(prog, ref):
    """The numbers that can be compared, from ``{"p0", "after": [p1, p2,
    p3], "losses", "delta_norms"}`` of the program and of the reference:

    * ``loss``: the largest relative gap of a round's mean loss;
      ``loss1`` the first round's alone; ``dnorm`` and ``dnorm1`` the
      same for the clients' local update norm (the program's
      ``delta_norm`` metric);
    * ``step1``: the worst leaf's gap of the first PS increment's norm;
      ``step1_med`` the median leaf's, ``step1_all`` the whole
      increment's;
    * ``change3``, ``change3_med``, ``change3_all``: the same for the
      change after three rounds.

    * ``p0_gap``: the largest gap between the program's initial weights
      and the reference's, as a share of the leaf's largest weight.

    Leaves whose first reference increment is under a thousandth of the
    median leaf's are left out: they move by rounding alone."""
    out = {"p0_gap": max(
        float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))))
        / max(float(np.max(np.abs(b))), 1e-30)
        for a, b in zip(jax.tree.leaves(prog["p0"]), jax.tree.leaves(ref["p0"]))
    )}
    ref1 = _leaf_norms(ref["p0"], ref["after"][0])
    med = float(np.median(ref1))
    keep = [r >= 1e-3 * med for r in ref1]
    for name, i in (("step1", 0), ("change3", 2)):
        pn = _leaf_norms(prog["p0"], prog["after"][i])
        rn = _leaf_norms(ref["p0"], ref["after"][i])
        gaps = leaf_gaps(pn, rn, keep)
        out[name] = max(gaps)
        out[f"{name}_med"] = float(np.median(gaps))
        out[f"{name}_all"] = _norm_gap(pn, rn, keep)
    for name, key in (("loss", "losses"), ("dnorm", "delta_norms")):
        got = np.asarray(prog[key], np.float64)
        want = np.asarray(ref[key], np.float64)
        rel = np.abs(got - want) / np.abs(want)
        finite = bool(np.all(np.isfinite(got)))
        out[name] = float(np.max(rel)) if finite else float("inf")
        out[f"{name}1"] = float(rel[0]) if finite else float("inf")
    return out
