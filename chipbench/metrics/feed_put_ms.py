"""``DataFeeder.feed`` from inside, the device's half: the ``put`` scope
(``jnp.asarray`` of the stacked array: the hand-over to the transfer,
and whatever it waits for), mean per step of the window; spans chosen
as in ``feed_stack_ms``, whose reader does the sum."""
from chipbench import harness

_spans = harness.load_module(harness.reader_path("feed_stack_ms"),
                             "chipbench_metric_feed_stack_ms")


def read(ctx):
    return _spans.mean_span_ms(ctx, "feed/convert/put")
