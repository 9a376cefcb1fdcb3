"""Share of the per-layer span (the traced slice) the engine spent
waiting on an empty candidate feed: growth of
``dwpa_feed_consumer_starve_seconds`` (all feeds) over it, over its
seconds."""


def read(run):
    s0, s1 = run.window.starve
    if s0 is None or s1 is None:
        return None
    return (s1 - s0) / run.layer_s
