"""Host time of the OPT-alpha solves per round of the window, in ms: the
program's own ``obs`` spans of category ``solve`` (recorded by the
``Tracer`` passed to the policy), summed and divided by the window's
rounds.  None where the window holds no solve."""


def read(art):
    solves = [s.dur_ns for s in art["spans"] if s.cat == "solve"]
    if not solves or art["window_rounds"] <= 0:
        return None
    return sum(solves) / 1e6 / art["window_rounds"]
