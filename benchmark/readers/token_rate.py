"""Output tokens received by the clients inside the window, over the
window's seconds: the whole cell's rate, not per chip."""


def read(cap):
    toks, _ = cap.window_tokens()
    return toks / cap.seconds if toks else None
