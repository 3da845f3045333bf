"""Output tokens the engine handed back inside the window, of every
request, over the window (host clock)."""


def read(rec):
    return rec["output_tokens"] / rec["window_s"]
