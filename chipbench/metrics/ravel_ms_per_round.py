"""Device time of the delta buffer's ravel and the increment's unravel
per traced round, in ms: the self time of the ops whose name-scope path
holds ``ravel`` (``fl/simulator.py`` around ``utils/trees.py``'s
``stacked_ravel`` and ``tree_unravel``), over the traced rounds.  None
where no op carries the scope."""


def read(art):
    scopes = art.get("scopes")
    if not scopes or "ravel" not in scopes or art["traced_rounds"] <= 0:
        return None
    return 1e3 * scopes["ravel"] / art["traced_rounds"]
