"""``DataFeeder.feed`` from inside, the host's half: the ``stack`` scope
(a slot's rows assembled into one contiguous float32 array), mean per
step of the window.

Read from the program's span buffer: the run's last ``ctx["steps"]``
spans named ``feed`` are the window's feeds (the trainer feeds one batch
ahead, and the feed that finds the reader at its end records nothing),
and every ``feed/convert/stack`` span that starts inside one of them is
summed."""

import bisect


def mean_span_ms(ctx, name: str):
    from paddle_tpu import observe
    steps = ctx["steps"]
    spans = [s for s in observe.default_buffer().spans() if s[5] == "X"]
    feeds = sorted((s[1], s[1] + s[2]) for s in spans if s[0] == "feed")
    feeds = feeds[-steps:] if steps > 0 else []
    if len(feeds) < max(steps, 1):
        return None
    starts = [f[0] for f in feeds]
    total, found = 0.0, False
    for s in spans:
        if s[0] != name:
            continue
        i = bisect.bisect_right(starts, s[1]) - 1
        if i >= 0 and s[1] < feeds[i][1]:
            total += s[2]
            found = True
    return 1000.0 * total / steps if found else None


def read(ctx):
    return mean_span_ms(ctx, "feed/convert/stack")
