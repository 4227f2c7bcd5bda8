"""Faults planted in the program beneath the timed path.

``planted(fault)`` breaks one piece of the program while the context is
open: the check must then read ``correct`` false.  The round faults swap
``FLSimulator._round_math`` (what every engine's compiled chunk traces);
the OPT-alpha faults break the host solve that feeds it.  Used by the
calibration, to read each fault's numbers on the chip, and by the tests,
on the CPU.
"""
import contextlib
import functools

FAULTS = (
    "state_unchanged", "half_batch", "relay_dropped", "answer_altered",
    "alpha_unconverged", "alpha_altered",
)
# the share by which "answer_altered" scales the PS increment and
# "alpha_altered" the relay weights
ALTERED_BY = 0.10


def _broken_round(fault, orig):
    import jax
    import jax.numpy as jnp

    def broken(self, params, server_state, batch, tau, A, lr, active):
        if fault == "half_batch":  # the mean over the first half of each minibatch
            batch = jax.tree.map(lambda x: x[:, :, : x.shape[2] // 2], batch)
        if fault == "relay_dropped":  # each client's update reaches the PS alone
            A = jnp.eye(A.shape[0], dtype=A.dtype)
        new, state, metrics = orig(self, params, server_state, batch, tau, A, lr, active)
        if fault == "state_unchanged":
            new = params
        if fault == "answer_altered":  # the PS increment, off by ALTERED_BY
            new = jax.tree.map(lambda a, b: a + (1.0 + ALTERED_BY) * (b - a), params, new)
        return new, state, metrics

    return broken


def _no_sweeps(solve):
    @functools.wraps(solve)
    def unconverged(*args, **kwargs):
        return solve(*args, **{**kwargs, "sweeps": 0})

    return unconverged


@contextlib.contextmanager
def planted(fault: str):
    from repro.channels import scheduler
    from repro.core import opt_alpha
    from repro.fl.simulator import FLSimulator

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r} (known: {FAULTS})")
    if fault == "alpha_unconverged":  # the solve stops before its first sweep
        owner, names = opt_alpha, ("optimize", "optimize_masked")
        patch = {n: _no_sweeps(getattr(opt_alpha, n)) for n in names}
    elif fault == "alpha_altered":  # the relay weights, off by ALTERED_BY
        owner = scheduler.AdaptiveOptAlpha
        orig = owner.relay_matrix
        patch = {"relay_matrix": lambda self, state: orig(self, state) * (1.0 + ALTERED_BY)}
    else:
        owner = FLSimulator
        patch = {"_round_math": _broken_round(fault, FLSimulator._round_math)}
    saved = {n: getattr(owner, n) for n in patch}
    for n, f in patch.items():
        setattr(owner, n, f)
    try:
        yield
    finally:
        for n, f in saved.items():
            setattr(owner, n, f)
