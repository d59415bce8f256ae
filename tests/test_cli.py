"""CLI jobs end-to-end in-process (reference: TrainerMain.cpp:52-61 job
dispatch; job=infer mirrors paddle.v2.infer / capi serving)."""

import os

import numpy as np

from paddle_tpu import cli

CONFIG = """
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import layer

img = layer.data("image", paddle.data_type.dense_vector(16))
lbl = layer.data("label", paddle.data_type.integer_value(4))
h = layer.fc(img, 8, act=paddle.activation.Relu(), name="cli_h")
out = layer.fc(h, 4, act=paddle.activation.Softmax(), name="cli_out")
cost = layer.classification_cost(out, lbl, name="cost")
outputs = [out]
batch_size = 8

_rng = np.random.RandomState(0)
_data = [( _rng.rand(16).astype("float32"), int(_rng.randint(4)) )
         for _ in range(32)]

def reader():
    return iter(_data)

def infer_reader():
    return iter([(x,) for x, _ in _data])
"""


def _write_config(tmp_path):
    p = tmp_path / "conf.py"
    p.write_text(CONFIG)
    return str(p)


class TestCliJobs:
    def test_train_then_infer_from_saved(self, tmp_path):
        conf = _write_config(tmp_path)
        save_dir = str(tmp_path / "out")
        rc = cli.main(["train", f"--config={conf}", "--num_passes=1",
                       f"--save_dir={save_dir}"])
        assert rc == 0
        tar = os.path.join(save_dir, "pass-00000", "params.tar")
        assert os.path.exists(tar)
        out_npz = str(tmp_path / "preds.npz")
        rc = cli.main(["infer", f"--config={conf}",
                       f"--init_model_path={tar}",
                       f"--output_path={out_npz}", "--infer_limit=8"])
        assert rc == 0
        preds = np.load(out_npz)["cli_out"]
        assert preds.shape == (8, 4)
        np.testing.assert_allclose(preds.sum(-1), 1.0, rtol=1e-4)

    def test_job_time_measures(self, tmp_path, capsys):
        conf = _write_config(tmp_path)
        rc = cli.main(["time", f"--config={conf}", "--time_batches=2",
                       "--warmup_batches=1"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "ms/batch" in out and "examples/sec" in out

    def test_measure_time_returns_metrics(self, tmp_path):
        cfg = cli._load_config(_write_config(tmp_path))
        r = cli.measure_time(cfg, time_batches=2, warmup_batches=1)
        assert r["ms_per_batch"] > 0
        assert r["timed_batches"] == 2

    def test_infer_from_merged_model(self, tmp_path):
        import paddle_tpu as paddle
        from paddle_tpu import layer
        from paddle_tpu.io import merged
        img = layer.data("image", paddle.data_type.dense_vector(16))
        h = layer.fc(img, 8, act=paddle.activation.Relu(), name="cm_h")
        out = layer.fc(h, 4, act=paddle.activation.Softmax(), name="cm_out")
        params = paddle.parameters.create(out)
        model = str(tmp_path / "m.tar")
        merged.save_inference_model(model, out, params)

        conf = _write_config(tmp_path)
        out_npz = str(tmp_path / "preds.npz")
        rc = cli.main(["infer", f"--config={conf}", f"--model={model}",
                       f"--output_path={out_npz}", "--infer_limit=8"])
        assert rc == 0
        preds = np.load(out_npz)["cm_out"]
        assert preds.shape == (8, 4)


class TestCliServe:
    def test_serve_streams_jsonl_requests(self, tmp_path, monkeypatch,
                                          capsys):
        """job=serve: format-v3 artifact + JSONL stdin -> one JSONL
        result per request (continuous batching over the stdio stream),
        matching the engine's direct greedy output."""
        import io
        import json
        import sys as _sys

        import jax
        import jax.numpy as jnp
        from paddle_tpu.io import lm_serving
        from paddle_tpu.models import transformer

        cfg = transformer.TransformerConfig(
            vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=2,
            d_ff=32, max_len=32, dtype=jnp.float32, use_rope=True)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        model = str(tmp_path / "lm_v4.tar")
        lm_serving.save_lm_artifact(model, params, cfg, batch=2,
                                    prompt_len=4, cache_len=24,
                                    engine_buckets=(8,),
                                    engine_block_size=8)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 40, n).tolist() for n in (4, 7)]
        lines = [json.dumps({"prompt": p, "max_new": 5})
                 for p in prompts]
        lines.append(json.dumps({"prompt": [], "max_new": 5}))  # bad
        monkeypatch.setattr(_sys, "stdin",
                            io.StringIO("\n".join(lines) + "\n"))
        rc = cli.main(["serve", f"--model={model}"])
        assert rc == 0
        out = [json.loads(l) for l in
               capsys.readouterr().out.strip().splitlines()]
        results = {r["id"]: r for r in out if "id" in r}
        errors = [r for r in out if "error" in r]
        assert len(results) == 2 and len(errors) == 1
        assert "empty prompt" in errors[0]["error"]
        want = {i: np.asarray(transformer.generate(
            params, jnp.asarray([p], jnp.int32), cfg, max_new=5))[0]
            for i, p in enumerate(prompts)}
        for i, p in enumerate(prompts):
            assert results[i]["finish_reason"] == "max_tokens"
            assert results[i]["tokens"] == want[i][len(p):].tolist()
            assert results[i]["ttft_ms"] > 0

    def test_serve_rejects_lockstep_artifact(self, tmp_path, capsys):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.io import lm_serving
        from paddle_tpu.models import transformer

        cfg = transformer.TransformerConfig(
            vocab=40, d_model=16, n_heads=2, n_layers=2, d_ff=32,
            max_len=32, dtype=jnp.float32)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        model = str(tmp_path / "lm_v1.tar")
        lm_serving.save_lm_artifact(model, params, cfg, batch=1,
                                    prompt_len=4, cache_len=12)
        rc = cli.main(["serve", f"--model={model}"])
        assert rc == 1
        assert "engine_buckets" in capsys.readouterr().err

    def test_serve_tenant_tier_fields_and_budget_flags(
            self, tmp_path, monkeypatch, capsys):
        """job=serve on a paged artifact: JSONL requests may carry
        tenant/tier, --tenant-budget caps a tenant, and a malformed
        tier comes back as a counted error line — never a traceback."""
        import io
        import json
        import sys as _sys

        import jax
        import jax.numpy as jnp
        from paddle_tpu.io import lm_serving
        from paddle_tpu.models import transformer

        cfg = transformer.TransformerConfig(
            vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=2,
            d_ff=32, max_len=32, dtype=jnp.float32, use_rope=True)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        model = str(tmp_path / "lm_v4.tar")
        lm_serving.save_lm_artifact(model, params, cfg, batch=2,
                                    prompt_len=4, cache_len=32,
                                    engine_buckets=(8,),
                                    engine_block_size=8)
        rng = np.random.RandomState(0)
        lines = [
            json.dumps({"prompt": rng.randint(0, 40, 5).tolist(),
                        "max_new": 4, "tenant": "acme",
                        "tier": "latency"}),
            json.dumps({"prompt": rng.randint(0, 40, 5).tolist(),
                        "max_new": 4, "tenant": "bulk",
                        "tier": "batch"}),
            json.dumps({"prompt": rng.randint(0, 40, 5).tolist(),
                        "max_new": 4, "tier": "turbo"}),   # malformed
        ]
        monkeypatch.setattr(_sys, "stdin",
                            io.StringIO("\n".join(lines) + "\n"))
        rc = cli.main(["serve", f"--model={model}",
                       "--tenant-budget", "acme=64"])
        assert rc == 0
        out = [json.loads(l) for l in
               capsys.readouterr().out.strip().splitlines()]
        results = [r for r in out if "id" in r]
        errors = [r for r in out if "error" in r]
        assert len(results) == 2
        assert len(errors) == 1 and "tier" in errors[0]["error"]

    def test_serve_malformed_tenant_budget_flag(self, tmp_path,
                                                capsys):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.io import lm_serving
        from paddle_tpu.models import transformer

        cfg = transformer.TransformerConfig(
            vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=2,
            d_ff=32, max_len=32, dtype=jnp.float32, use_rope=True)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        model = str(tmp_path / "lm_v4b.tar")
        lm_serving.save_lm_artifact(model, params, cfg, batch=2,
                                    prompt_len=4, cache_len=32,
                                    engine_buckets=(8,),
                                    engine_block_size=8)
        rc = cli.main(["serve", f"--model={model}",
                       "--tenant-budget", "acme"])
        assert rc == 1
        assert "TENANT=TOKENS" in capsys.readouterr().err

    def test_serve_streams_results_while_stdin_open(self, tmp_path):
        """A streaming client that holds the pipe open must get each
        result as its request completes — the engine steps while stdin
        is idle (regression: decode used to stall until EOF)."""
        import json
        import subprocess
        import sys as _sys

        import jax
        import jax.numpy as jnp
        from paddle_tpu.io import lm_serving
        from paddle_tpu.models import transformer

        cfg = transformer.TransformerConfig(
            vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=2,
            d_ff=32, max_len=32, dtype=jnp.float32, use_rope=True)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        model = str(tmp_path / "lm_v4.tar")
        lm_serving.save_lm_artifact(model, params, cfg, batch=2,
                                    prompt_len=4, cache_len=24,
                                    engine_buckets=(8,),
                                    engine_block_size=8)
        p = subprocess.Popen(
            [_sys.executable, "-m", "paddle_tpu", "serve",
             f"--model={model}"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
        try:
            # the first stdout line announces readiness: the device,
            # and the kernel path every compiled program placed
            ready = json.loads(p.stdout.readline())["replica_ready"]
            assert ready["device"]["platform"] == "cpu"
            assert ready["pallas"] == "off"
            assert ready["kernel_paths"]["decode"] == {
                "attention": "xla", "sampler": "xla"}
            assert ready["compile_cache"]["dir"]
            p.stdin.write(json.dumps(
                {"prompt": [1, 2, 3], "max_new": 4}) + "\n")
            p.stdin.flush()
            # stdin stays OPEN: the first result must arrive anyway
            first = json.loads(p.stdout.readline())
            assert first["id"] == 0 and len(first["tokens"]) == 4
            p.stdin.write(json.dumps(
                {"prompt": [5, 6], "max_new": 3}) + "\n")
            p.stdin.close()
            second = json.loads(p.stdout.readline())
            assert second["id"] == 1 and len(second["tokens"]) == 3
            assert p.wait(timeout=60) == 0
        finally:
            p.kill()

    def test_serve_sigterm_drains_gracefully(self, tmp_path):
        """SIGTERM mid-request = graceful drain: the in-flight request
        finishes, its result is emitted, and the process exits 0 (the
        replica-drain contract the fleet router stands on — the old
        behavior just died, losing the request). The health endpoint
        pins that the request was accepted BEFORE the signal."""
        import json
        import re
        import signal
        import subprocess
        import sys as _sys
        import time
        import urllib.request

        import jax
        import jax.numpy as jnp
        from paddle_tpu.io import lm_serving
        from paddle_tpu.models import transformer

        cfg = transformer.TransformerConfig(
            vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=2,
            d_ff=32, max_len=64, dtype=jnp.float32, use_rope=True)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        model = str(tmp_path / "lm_v4_drain.tar")
        lm_serving.save_lm_artifact(model, params, cfg, batch=2,
                                    prompt_len=4, cache_len=64,
                                    engine_buckets=(8,),
                                    engine_block_size=8)
        p = subprocess.Popen(
            [_sys.executable, "-m", "paddle_tpu", "serve",
             f"--model={model}", "--health_port=0"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
        try:
            p.stdin.write(json.dumps(
                {"prompt": [1, 2, 3], "max_new": 40}) + "\n")
            p.stdin.flush()
            url = None
            while url is None:          # jax may log to stderr first
                line = p.stderr.readline()
                if not line and p.poll() is not None:
                    raise AssertionError(
                        f"serve process died before announcing its "
                        f"health endpoint (rc={p.poll()})")
                m = re.search(r"(http://[\d.:]+)/metrics", line)
                url = m and m.group(1)
            deadline = time.time() + 120
            doc = {}
            while time.time() < deadline:
                doc = json.loads(urllib.request.urlopen(
                    url + "/healthz", timeout=5).read())
                if doc.get("requests", 0) >= 1:
                    break
                time.sleep(0.05)
            assert doc.get("requests", 0) >= 1, doc
            p.send_signal(signal.SIGTERM)
            assert "replica_ready" in json.loads(p.stdout.readline())
            out = json.loads(p.stdout.readline())
            assert p.wait(timeout=120) == 0
            assert out["finish_reason"] == "max_tokens"
            assert len(out["tokens"]) == 40
        finally:
            p.kill()
