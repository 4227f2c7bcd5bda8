"""Device idle per traced round, in ms, during which the innermost open
host span is the trainer's result ``fetch`` or a ``control`` span
(publish, stop check) between bursts (``launch/train.py``), placed on the
device clock as ``idle_staging_ms_per_round`` is.  None without spans on
that clock."""


def read(art):
    idle = art.get("idle_buckets")
    if idle is None or art["traced_rounds"] <= 0:
        return None
    return 1e3 * idle["burst_edge"] / art["traced_rounds"]
