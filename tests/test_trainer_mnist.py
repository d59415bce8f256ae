"""The minimum end-to-end slice (SURVEY.md §7.6): MNIST LeNet-5 through the
full v2-style API — layers → trainer → optimizer → evaluator → checkpoint →
infer. Mirrors the reference's book tests
(python/paddle/v2/framework/tests/book/test_recognize_digits_conv.py) and
v1_api_demo/mnist."""

import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import evaluator, layer, networks
from paddle_tpu.io import checkpoint
from paddle_tpu.utils.rng import KeySource


def _lenet(img):
    c1 = networks.simple_img_conv_pool(img, filter_size=5, num_filters=8,
                                       pool_size=2, num_channel=1,
                                       act=paddle.activation.Relu(),
                                       name="c1")
    c2 = networks.simple_img_conv_pool(c1, filter_size=5, num_filters=16,
                                       pool_size=2,
                                       act=paddle.activation.Relu(),
                                       name="c2")
    fc1 = layer.fc(c2, 64, act=paddle.activation.Relu(), name="fc1")
    return layer.fc(fc1, 10, act=paddle.activation.Softmax(), name="pred")


@pytest.fixture(scope="module")
def trained():
    paddle.init(seed=1234)
    img = layer.data("pixel", paddle.data_type.dense_vector(784))
    lbl = layer.data("label", paddle.data_type.integer_value(10))
    pred = _lenet(img)
    cost = layer.classification_cost(pred, lbl, name="cost")
    err = evaluator.classification_error(pred, lbl, name="err")
    params = paddle.parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(
            momentum=0.9, learning_rate=0.05),
        extra_layers=[err])

    costs = []

    def handler(e):
        if isinstance(e, paddle.event.EndIteration):
            costs.append(e.cost)

    reader = paddle.batch(
        paddle.reader.shuffle(paddle.dataset.mnist.train(), 2048),
        batch_size=64)
    trainer.train(reader=reader, num_passes=1, event_handler=handler)
    return trainer, params, pred, img, costs


def test_training_converges(trained):
    trainer, params, pred, img, costs = trained
    first = np.mean(costs[:8])
    last = np.mean(costs[-8:])
    assert first > 2 * last, f"no convergence: first {first} last {last}"
    assert last < 0.5


def test_evaluator_error_low(trained):
    trainer, params, pred, img, costs = trained
    res = trainer.test(paddle.batch(paddle.dataset.mnist.test(), 64))
    metrics = res.metrics
    assert metrics["err"] < 0.15, metrics
    assert res.cost < 0.6


def test_infer_matches_training(trained):
    trainer, params, pred, img, costs = trained
    samples = [(x,) for x, y in list(paddle.dataset.mnist.test()())[:32]]
    labels = [y for x, y in list(paddle.dataset.mnist.test()())[:32]]
    probs = paddle.infer(output_layer=pred, parameters=params, input=samples)
    assert probs.shape == (32, 10)
    acc = (probs.argmax(-1) == np.array(labels)).mean()
    assert acc > 0.8


def test_checkpoint_roundtrip(trained, tmp_path):
    trainer, params, pred, img, costs = trained
    d = str(tmp_path / "ckpt")
    checkpoint.save_checkpoint(d, 42, params.values, trainer.opt_state,
                               params.state)
    path = checkpoint.latest_checkpoint(d)
    step, p2, o2, s2 = checkpoint.load_checkpoint(
        path, params.values, trainer.opt_state, params.state)
    assert step == 42
    np.testing.assert_allclose(np.asarray(p2["fc1.w"]), params["fc1.w"])


def test_params_tar_roundtrip(trained, tmp_path):
    trainer, params, pred, img, costs = trained
    f = tmp_path / "params.tar"
    with open(f, "wb") as fh:
        params.to_tar(fh)
    with open(f, "rb") as fh:
        p2 = paddle.parameters.Parameters.from_tar(fh)
    np.testing.assert_allclose(p2["pred.w"], params["pred.w"])


class TestFeedUnderStep:
    """The synchronous feed path of ``SGD.train``: batch 0 is fed before
    the loop, batch N+1 between step N's dispatch and its host sync, on
    the trainer's own thread (the reference's double-buffering data
    providers, PyDataProvider2.cpp:195, as an order of three statements).
    Every case goes through ``SGD.train`` on a small dense model with a
    reader of whole batches that notes its pulls, a feeder that notes its
    feeds and a handler that notes the events."""

    FEEDING = {"fus_x": 0, "fus_y": 1}

    @staticmethod
    def _trainer(parallel=None):
        x = layer.data("fus_x", paddle.data_type.dense_vector(12))
        y = layer.data("fus_y", paddle.data_type.integer_value(4))
        h = layer.fc(x, 16, act=paddle.activation.Relu(), name="fus_h")
        out = layer.fc(h, 4, act=paddle.activation.Softmax(), name="fus_o")
        cost = layer.classification_cost(out, y, name="fus_c")
        params = paddle.parameters.create(cost, KeySource(77))
        return paddle.trainer.SGD(
            cost=cost, parameters=params, parallel=parallel,
            update_equation=paddle.optimizer.Momentum(learning_rate=0.05,
                                                      momentum=0.9))

    @staticmethod
    def _batches(n, batch=8):
        rng = np.random.RandomState(5)
        return [[(rng.randn(12).astype(np.float32), int(rng.randint(4)))
                 for _ in range(batch)] for _ in range(n)]

    def _run(self, tr, batches, log, fail_at=None, on_end=None):
        """One pass over ``batches``; ``log`` takes ("pull", k), ("feed",
        k), ("eof",), ("begin", n), ("end", n) in the order they happen.
        ``fail_at=k``: the reader raises where batch k is due."""
        def reader():
            for k, b in enumerate(batches):
                if k == fail_at:
                    raise OSError(f"batch {k} unreadable")
                log.append(("pull", k))
                yield b
            log.append(("eof",))

        real = tr._feeder(self.FEEDING)
        fed = iter(range(len(batches)))

        class SpyFeeder:
            def feed(self, b):
                log.append(("feed", next(fed)))
                return real.feed(b)

        tr._feeder = lambda feeding: SpyFeeder()

        def handler(e):
            if isinstance(e, paddle.event.BeginIteration):
                log.append(("begin", e.batch_id))
            elif isinstance(e, paddle.event.EndIteration):
                log.append(("end", e.batch_id))
                if on_end is not None:
                    on_end(e)

        tr.train(reader, num_passes=1, event_handler=handler,
                 feeding=self.FEEDING)

    def test_next_batch_is_fed_inside_the_iteration(self):
        """Batch N+1 is pulled and fed after BeginIteration(N) and before
        EndIteration(N); batch N+2 is not."""
        log = []
        self._run(self._trainer(), self._batches(3), log)
        assert log == [
            ("pull", 0), ("feed", 0),
            ("begin", 0), ("pull", 1), ("feed", 1), ("end", 0),
            ("begin", 1), ("pull", 2), ("feed", 2), ("end", 1),
            ("begin", 2), ("eof",), ("end", 2)]

    def test_bitwise_equal_to_feed_then_dispatch_then_sync(self):
        """Same work, same results: the losses of K steps and the
        parameters at every EndIteration(N) are bitwise those of the
        parent's order (feed, then dispatch, then sync), spelled out here
        on a second trainer with the same initial values."""
        import jax.numpy as jnp
        from paddle_tpu.utils.rng import global_key_source
        batches = self._batches(5)

        ref = self._trainer()
        feeder, ks = ref._feeder(self.FEEDING), global_key_source()
        ref_losses, ref_params = [], []
        for b in batches:
            feeds = feeder.feed(b)
            step_fn = ref._pick_train_step(feeds)
            (loss, ref.parameters.values, ref.opt_state,
             ref.parameters.state, _) = step_fn(
                ref.parameters.values, ref.opt_state, ref.parameters.state,
                feeds, jnp.asarray(ref._step, jnp.int32),
                ks.step("dropout", ref._step))
            ref._step += 1
            ref_losses.append(float(loss))
            ref_params.append({k: np.asarray(v) for k, v in
                               ref.parameters.values.items()})

        tr = self._trainer()
        losses, seen = [], []

        def on_end(e):
            losses.append(e.cost)
            # the outputs of step N, with exactly N+1 steps dispatched
            assert tr._step == e.batch_id + 1
            seen.append({k: np.asarray(v) for k, v in
                         tr.parameters.values.items()})

        self._run(tr, batches, [], on_end=on_end)
        assert losses == ref_losses
        assert len(seen) == len(ref_params) == 5
        for n, (got, want) in enumerate(zip(seen, ref_params)):
            assert got.keys() == want.keys()
            for k in want:
                np.testing.assert_array_equal(got[k], want[k],
                                              err_msg=f"step {n} {k}")

    @pytest.mark.parametrize("breaks", ["reader", "feeder"])
    def test_failure_on_next_batch_surfaces_after_the_step(self, breaks):
        """A reader (or feeder) that fails on batch N+1 delivers
        EndIteration(N) first, then the exception; no further step."""
        log, tr = [], self._trainer()
        batches = self._batches(4)
        if breaks == "reader":
            with pytest.raises(OSError, match="batch 2 unreadable"):
                self._run(tr, batches, log, fail_at=2)
            tail = [("begin", 1), ("end", 1)]
        else:
            batches[2] = [(np.zeros(12, np.float32),)]   # no label column
            with pytest.raises(Exception) as ei:
                self._run(tr, batches, log)
            assert not isinstance(ei.value, (OSError, StopIteration))
            tail = [("begin", 1), ("pull", 2), ("feed", 2), ("end", 1)]
        assert log == [("pull", 0), ("feed", 0),
                       ("begin", 0), ("pull", 1), ("feed", 1), ("end", 0)
                       ] + tail
        assert tr._step == 2

    @pytest.mark.parametrize("n", [0, 1])
    def test_empty_and_one_batch_readers(self, n):
        """An empty reader trains nothing and never reaches the feeder; a
        one-batch reader trains exactly one step."""
        log, tr = [], self._trainer()
        self._run(tr, self._batches(n), log)
        assert tr._step == n
        assert log == [[("eof",)],
                       [("pull", 0), ("feed", 0), ("begin", 0), ("eof",),
                        ("end", 0)]][n]

    @pytest.mark.parametrize("sharded", [False, True])
    def test_scopes_top_level_one_feed_span_per_step(self, sharded):
        """``feed`` and ``host_sync`` stay top-level scopes, closed in the
        order feed(0), then per step: dispatch(N), feed(N+1),
        host_sync(N); one ``feed`` span a step and none for the pull that
        ends the pass. Under ``parallel`` the sharded put moves with the
        feed (``feed/transfer``)."""
        from paddle_tpu import observe, parallel
        from paddle_tpu.core import place
        par = None
        if sharded:
            par = parallel.DistConfig(
                place.make_mesh((4,), (place.AXIS_DATA,)))
        tr = self._trainer(par)
        buf = observe.default_buffer()
        buf.clear()
        self._run(tr, self._batches(3), [])
        names = [s[0] for s in buf.spans() if s[5] == "X"]
        order = [n for n in names if n in
                 ("feed", "train_step/dispatch", "host_sync")]
        assert order == (["feed"] +
                         ["train_step/dispatch", "feed", "host_sync"] * 2 +
                         ["train_step/dispatch", "host_sync"])
        assert names.count("feed/convert") == 3
        assert names.count("feed/transfer") == (3 if sharded else 0)
        assert not [n for n in names
                    if n.endswith(("/feed", "/host_sync"))]


class TestGradAccum:
    def _train(self, accum, batches=6, batch=32):
        import paddle_tpu as paddle
        from paddle_tpu import layer
        from paddle_tpu.dataset import synthetic
        x = layer.data("ga_x", paddle.data_type.dense_vector(20))
        y = layer.data("ga_y", paddle.data_type.integer_value(5))
        h = layer.fc(x, 16, act=paddle.activation.Relu(),
                     name="ga_h")
        out = layer.fc(h, 5, act=paddle.activation.Softmax(),
                       name="ga_o")
        cost = layer.classification_cost(out, y, name="ga_c")
        params = paddle.parameters.create(cost, KeySource(123))
        tr = paddle.trainer.SGD(
            cost=cost, parameters=params,
            update_equation=paddle.optimizer.Momentum(learning_rate=0.05,
                                                      momentum=0.9),
            grad_accum_steps=accum)
        reader = paddle.reader.firstn(
            synthetic.classification(batches * batch, 20, 5, seed=9), 
            batches * batch)
        losses = []
        tr.train(reader=paddle.batch(reader, batch), num_passes=1,
                 feeding={"ga_x": 0, "ga_y": 1},
                 event_handler=lambda e: losses.append(e.cost)
                 if isinstance(e, paddle.event.EndIteration) else None)
        return losses, tr.parameters

    def test_accum_matches_plain(self):
        """grad_accum_steps=4 must reproduce accum=1 numerics on a
        BN-free model (the optimizer sees the same full-batch mean
        gradient; only summation order differs)."""
        l1, p1 = self._train(1)
        l4, p4 = self._train(4)
        np.testing.assert_allclose(l1, l4, rtol=2e-4, atol=2e-5)
        for name in p1.names():
            a = np.asarray(p1[name])
            b = np.asarray(p4[name])
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5,
                                       err_msg=name)

    def test_invalid_steps_rejected(self):
        import paddle_tpu as paddle
        with pytest.raises(ValueError, match="grad_accum_steps"):
            self._train(0)

    def test_ragged_tail_falls_back_to_plain_step(self):
        """drop_last=False remainder batches must not crash the accum
        path — they route to the unaccumulated step."""
        import paddle_tpu as paddle
        from paddle_tpu import layer
        from paddle_tpu.dataset import synthetic
        x = layer.data("gar_x", paddle.data_type.dense_vector(8))
        y = layer.data("gar_y", paddle.data_type.integer_value(3))
        out = layer.fc(x, 3, act=paddle.activation.Softmax(), name="gar_o")
        cost = layer.classification_cost(out, y, name="gar_c")
        params = paddle.parameters.create(cost, KeySource(5))
        tr = paddle.trainer.SGD(
            cost=cost, parameters=params,
            update_equation=paddle.optimizer.SGD(learning_rate=0.1),
            grad_accum_steps=4)
        reader = paddle.reader.firstn(
            synthetic.classification(90, 8, 3, seed=2), 90)
        costs = []
        tr.train(
            reader=paddle.batch(reader, 32, drop_last=False),
            num_passes=1, feeding={"gar_x": 0, "gar_y": 1},
            event_handler=lambda e: costs.append(e.cost)
            if isinstance(e, paddle.event.EndIteration) else None)
        assert len(costs) == 3              # 32 + 32 + 26
        assert all(np.isfinite(c) for c in costs)
