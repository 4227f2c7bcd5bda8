"""Device time of the clients' local training per traced round, in ms:
the self time of the ops whose name-scope path holds ``local_train``
(``fl/simulator.py``), between the first and last mark of the traced
window, over the traced rounds.  None where no op carries the scope."""


def read(art):
    scopes = art.get("scopes")
    if not scopes or "local_train" not in scopes or art["traced_rounds"] <= 0:
        return None
    return 1e3 * scopes["local_train"] / art["traced_rounds"]
