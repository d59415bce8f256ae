"""``engine.precompile()`` from inside: every prefill program of the
chunk grid (``precompile/prefill``) and the decode program
(``precompile/decode``) run once; scope totals as in
``artifact_read_s``, whose reader does the sum."""
from chipbench import harness

_scopes = harness.load_module(harness.reader_path("artifact_read_s"),
                              "chipbench_metric_artifact_read_s")


def read(ctx):
    return _scopes.read(ctx, ("precompile/prefill", "precompile/decode"))
