"""Output tokens that reached the host in the window, over the window."""


def read(r):
    return r.tokens / r.window_s if r.window_s > 0 and r.tokens else None
