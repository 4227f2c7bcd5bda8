"""Time the consumer blocked on staging per round of the window, in ms:
the prefetcher's ``wait_s`` (staging with no dispatch in flight to hide
it), summed over the window's bursts and divided by its rounds."""


def read(art):
    if art["window_rounds"] <= 0:
        return None
    return 1e3 * art["stage_wait_s"] / art["window_rounds"]
