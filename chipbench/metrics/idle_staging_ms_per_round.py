"""Device idle per traced round, in ms, during which the innermost open
host span is the prefetcher's ``stage`` or ``h2d`` or an OPT-alpha
``solve``: the program's spans placed on the device clock by the marks
(``trace_align``).  None without spans on that clock (uncertainty past
1 ms, or no tracer on the trainer)."""


def read(art):
    idle = art.get("idle_buckets")
    if idle is None or art["traced_rounds"] <= 0:
        return None
    return 1e3 * idle["staging"] / art["traced_rounds"]
