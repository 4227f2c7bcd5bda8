"""Device idle per traced round, in ms, under no program span at all:
host work the program does not trace (Python glue between spans, the
garbage collector), placed as ``idle_staging_ms_per_round`` is.  None
without spans on the device clock."""


def read(art):
    idle = art.get("idle_buckets")
    if idle is None or art["traced_rounds"] <= 0:
        return None
    return 1e3 * idle["unattributed"] / art["traced_rounds"]
