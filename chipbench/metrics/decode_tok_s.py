"""Output tokens received on the host in the window, per second per chip.
The window opens and closes at a fetch, so it holds whole steps."""


def read(rec):
    if not rec.get("window_s") or rec.get("tokens_in_window") is None:
        return None
    return rec["tokens_in_window"] / rec["window_s"] / rec["chips"]
