"""Multi-host runtime: N-process cluster simulation via the local launcher
(the no-real-cluster strategy of trainer/tests/test_CompareSparse.cpp:65 —
in-process pservers — one level up: separate OS processes joined by
jax.distributed), plus hybrid ICI x DCN meshes and the master-fed trainer."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _require_multiprocess_cpu():
    """Several jaxlib releases accept jax.distributed.initialize on CPU
    but die at dispatch with "Multiprocess computations aren't
    implemented on the CPU backend". Feature-detect (one cached
    2-process probe, launch.multiprocess_cpu_supported) and skip with
    the reason so the slow lane is signal, not noise — the
    single-process dryrun_multichip proofs (tests/test_parallel.py)
    stay the tier-1 coverage for multi-chip semantics."""
    from paddle_tpu.runtime import launch
    if not launch.multiprocess_cpu_supported():
        pytest.skip(
            "this jaxlib cannot execute multi-process computations on "
            "the CPU backend (probe failed; single-process "
            "dryrun_multichip proofs cover the tier-1 semantics)")


WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    import paddle_tpu.distributed as dist
    dist.init()
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    nglobal = len(jax.devices())
    nlocal = len(jax.local_devices())
    assert nglobal == 8 and nlocal == 4, (nglobal, nlocal)

    # hybrid mesh: dcn axis across the 2 processes, data axis within
    from paddle_tpu import distributed
    mesh = distributed.hybrid_mesh((4,), ("data",))
    assert dict(mesh.shape) == {{"dcn": 2, "data": 4}}, mesh.shape

    # a cross-host psum over both axes: every device contributes 1
    ones = jnp.ones((8,), jnp.float32)
    sharded = jax.device_put(
        ones, NamedSharding(mesh, P(("dcn", "data"))))

    def f(x):
        return jax.lax.psum(jnp.sum(x), ("dcn", "data"))

    total = jax.jit(jax.shard_map(f, mesh=mesh,
                              in_specs=P(("dcn", "data")), out_specs=P()
                              ))(sharded)
    # the psum result is replicated; every process sees 8.0
    assert float(total) == 8.0, float(total)
    out_dir = os.environ["TEST_OUT_DIR"]
    rank = jax.process_index()
    with open(os.path.join(out_dir, f"ok_{{rank}}"), "w") as fh:
        fh.write(f"{{float(total)}} {{nglobal}} {{nlocal}}")
    print("worker", rank, "OK", flush=True)
""")


@pytest.mark.slow
class TestMultiProcessCluster:
    def test_two_process_psum(self, tmp_path):
        """2 processes x 4 virtual CPU devices join one cluster; a hybrid
        dcn x data mesh spans them and a global psum sees all 8 devices."""
        _require_multiprocess_cpu()
        from paddle_tpu.runtime import launch

        worker = tmp_path / "worker.py"
        worker.write_text(WORKER.format(repo=REPO))
        rcs = launch.launch_local(
            2, [str(worker)], devices_per_proc=4,
            env_extra={"TEST_OUT_DIR": str(tmp_path)}, timeout=300)
        assert rcs == [0, 0], rcs
        for rank in range(2):
            body = (tmp_path / f"ok_{rank}").read_text()
            assert body.startswith("8.0"), body


class TestSshLaunch:
    """launch_ssh fans one worker per host over an ssh-like command with
    the PADDLE_* env contract injected on the remote command line. The
    ssh binary is substituted with a local shim (drops the host arg,
    execs the command) so the mechanics are tested without a cluster."""

    def _shim(self, tmp_path):
        shim = tmp_path / "fakessh"
        shim.write_text("#!/bin/bash\nshift\nexec bash -c \"$*\"\n")
        shim.chmod(0o755)
        return str(shim)

    def test_env_contract_and_ranks(self, tmp_path):
        from paddle_tpu.runtime import launch
        worker = tmp_path / "w.py"
        worker.write_text(
            "import os\n"
            "d = os.environ\n"
            "open(os.path.join(d['OUT'], 'r' + d['PADDLE_PROCESS_ID']),"
            " 'w').write('|'.join([d['PADDLE_COORDINATOR'],"
            " d['PADDLE_NUM_PROCESSES'], os.getcwd()]))\n")
        rcs = launch.launch_ssh(
            ["hostA", "hostB"], ["python", str(worker)], port=7070,
            workdir=str(tmp_path), env_extra={"OUT": str(tmp_path)},
            ssh_cmd=(self._shim(tmp_path),), timeout=60)
        assert rcs == [0, 0], rcs
        for rank in range(2):
            coord, n, cwd = (tmp_path / f"r{rank}").read_text().split("|")
            assert coord == "hostA:7070" and n == "2"
            assert cwd == str(tmp_path)       # workdir honored

    def test_remote_failure_propagates(self, tmp_path):
        from paddle_tpu.runtime import launch
        rcs = launch.launch_ssh(
            ["hostA"], ["bash", "-c", "exit 3"],
            ssh_cmd=(self._shim(tmp_path),), timeout=60)
        assert rcs == [3]

    def test_timeout_tears_down_remote_tree(self, tmp_path):
        """On _wait_all timeout the REMOTE worker tree must die too, not
        just the local ssh client (ADVICE round-5): the wrapper's stdin
        watchdog sees the closed connection and kills the worker's
        process group — here a sleeper that would otherwise outlive the
        launcher by a minute (and keep holding the coordinator port)."""
        import time

        from paddle_tpu.runtime import launch

        pidfile = tmp_path / "worker.pid"
        worker = tmp_path / "sleeper.py"
        worker.write_text(
            "import os, time, sys\n"
            f"open({str(pidfile)!r}, 'w').write(str(os.getpid()))\n"
            "time.sleep(60)\n")
        t0 = time.time()
        rcs = launch.launch_ssh(
            ["hostA"], ["python", str(worker)],
            ssh_cmd=(self._shim(tmp_path),), timeout=2.0)
        assert rcs[0] != 0, rcs
        assert time.time() - t0 < 30          # did not sit out the sleep
        pid = int(pidfile.read_text())
        deadline = time.time() + 10
        alive = True
        while alive and time.time() < deadline:
            try:
                os.kill(pid, 0)
                time.sleep(0.2)
            except OSError:
                alive = False
        assert not alive, f"remote worker {pid} survived the teardown"

    def test_cli_hosts_mode(self, tmp_path, capsys):
        """--hosts routes main() through the ssh fan-out."""
        from paddle_tpu.runtime import launch
        out = tmp_path / "cli_out"
        rc = launch.main([
            "--hosts", "h0,h1", "--port", "7071",
            "--ssh-cmd", self._shim(tmp_path), "--timeout", "60",
            "bash", "-c",
            f"echo $PADDLE_PROCESS_ID:$PADDLE_COORDINATOR >> {out}"])
        assert rc == 0
        lines = sorted(out.read_text().split())
        assert lines == ["0:h0:7071", "1:h0:7071"]


class TestZeroCollectivePattern:
    """The ZeRO stages' compiled-HLO contracts on the virtual CPU mesh:
    the full-gradient all-reduce of classic DP disappears under zero>=1
    in favour of the reduce-scatter form (XLA:CPU emits it as the manual
    all-reduce-consumed-only-by-shard-slices pattern — the CPU pipeline
    lacks the reduce-scatter-creator pass; ``benchmarks/zero_bench.py
    --tpu-check`` and ``scaling_aot.py --zero1/2/3`` show the real
    XLA:TPU fused all-reduce-scatter) plus a param-sized post-update
    all-gather below stage 3; at stage 2 the contract extends to the
    accumulation path, and at stage 3 params enter the module as 1/N
    shards with only on-use all-gathers.
    ``parallel.spmd.zero_collective_evidence`` classifies all of it."""

    def _evidence(self, zero, accum=1):
        import jax
        import jax.numpy as jnp

        import paddle_tpu as paddle
        from paddle_tpu import layer, parallel
        from paddle_tpu.core import place
        from paddle_tpu.parallel import spmd
        from paddle_tpu.utils.rng import KeySource

        x = layer.data("x", paddle.data_type.dense_vector(8))
        lbl = layer.data("lbl", paddle.data_type.integer_value(3))
        h = layer.fc(x, 16, act=paddle.activation.Relu(), name="zh")
        out = layer.fc(h, 3, act=paddle.activation.Softmax(), name="zo")
        cost = layer.classification_cost(out, lbl, name="zcost")
        params = paddle.parameters.create(cost, KeySource(11))
        mesh = place.make_mesh((4,), (place.AXIS_DATA,))
        tr = paddle.trainer.SGD(
            cost=cost, parameters=params,
            update_equation=paddle.optimizer.Adam(learning_rate=0.05),
            parallel=parallel.data_parallel(mesh, zero=zero),
            grad_accum_steps=accum)
        feeds = tr._feeder(None).feed(
            [(np.random.RandomState(0).randn(8).astype(np.float32), 1)
             for _ in range(16)])
        feeds = jax.device_put(feeds, tr.parallel.feed_shardings(feeds))
        args = (tr.parameters.values, tr.opt_state, tr.parameters.state,
                feeds, jnp.asarray(0, jnp.int32),
                jax.random.PRNGKey(0))
        step = tr._accum_train_step if accum > 1 else tr._plain_train_step
        txt = step.lower(*args).compile().as_text()
        biggest = max(np.asarray(v).nbytes
                      for v in tr.parameters.values.values())
        return spmd.zero_collective_evidence(txt, biggest)

    def test_zero0_has_full_grad_all_reduce(self):
        ev = self._evidence(zero=0)
        assert ev["full_grad_all_reduce"] >= 1, ev
        assert ev["param_all_gather"] == 0, ev

    def test_zero1_reduce_scatters_and_gathers(self):
        ev = self._evidence(zero=1)
        assert ev["full_grad_all_reduce"] == 0, ev
        assert ev["reduce_scatter"] >= 1, ev
        assert ev["param_all_gather"] >= 1, ev

    def test_zero1_accum_step_same_pattern(self):
        ev = self._evidence(zero=1, accum=2)
        assert ev["full_grad_all_reduce"] == 0, ev
        assert ev["param_all_gather"] >= 1, ev

    def test_zero2_no_full_grad_all_reduce_anywhere(self):
        """Stage 2: the sharded-gradient contract holds on the plain AND
        the accumulation path — no gradient-sized all-reduce is consumed
        at full size anywhere (each microbatch reduce-scatters into the
        sharded carry; XLA may also choose the gather-the-activations
        strategy, which never materializes a full grad either). Params
        are still resident in full (that is stage 3's job)."""
        for accum in (1, 2):
            ev = self._evidence(zero=2, accum=accum)
            assert ev["full_grad_all_reduce"] == 0, (accum, ev)
            assert ev["resident_full_args"] >= 1, (accum, ev)

    def test_zero3_sharded_resident_params_gather_on_use(self):
        """Stage 3: no ENTRY argument is a full replicated parameter
        (params enter as 1/N zero_spec shards — per-device entry shapes
        prove residency), the all-gathers that exist are consumed by
        compute (gather-on-use), none flow straight to the output (the
        post-update regather of stages 1-2 is gone), and the gather's
        backward transpose reduce-scatters the grads — no full-gradient
        all-reduce."""
        for accum in (1, 2):
            ev = self._evidence(zero=3, accum=accum)
            assert ev["resident_full_args"] == 0, (accum, ev)
            assert ev["on_use_all_gather"] >= 1, (accum, ev)
            assert ev["output_all_gather"] == 0, (accum, ev)
            assert ev["full_grad_all_reduce"] == 0, (accum, ev)
            assert ev["reduce_scatter"] >= 1, (accum, ev)

    def test_zero0_has_full_resident_params(self):
        """The stage-3 discriminator is meaningful: classic DP shows
        replicated full-param entry args."""
        ev = self._evidence(zero=0)
        assert ev["resident_full_args"] >= 1, ev


class TestHybridMeshSingleProcess:
    def test_single_slice_falls_back_to_plain_mesh(self):
        from paddle_tpu import distributed
        mesh = distributed.hybrid_mesh((4, 2), ("data", "model"),
                                       num_slices=1)
        assert dict(mesh.shape) == {"data": 4, "model": 2}

    def test_shape_mismatch_raises(self):
        from paddle_tpu import distributed
        with pytest.raises(ValueError, match="devices"):
            distributed.hybrid_mesh((4,), ("data",), num_slices=3)


class TestMasterFedTrainer:
    """The go/master -> trainer integration: the reader leases tasks from
    the master; a consumer that dies mid-task loses its lease and the
    work is re-dispatched (task-lease fault tolerance, service.go:106)."""

    def _write_recordio(self, tmp_path, n=64):
        from paddle_tpu.runtime import recordio
        path = str(tmp_path / "data.rio")
        w = recordio.Writer(path, records_per_chunk=8)
        rng = np.random.RandomState(0)
        for i in range(n):
            import pickle
            w.write(pickle.dumps(
                (rng.rand(4).astype(np.float32), int(rng.randint(2)))))
        w.close()
        return path

    def test_trainer_trains_from_master_reader(self, tmp_path):
        import pickle

        import paddle_tpu as paddle
        from paddle_tpu import layer
        from paddle_tpu.runtime.master import MasterClient, MasterService
        from paddle_tpu.utils.rng import KeySource

        path = self._write_recordio(tmp_path)
        svc = MasterService(lease_seconds=30)
        svc.set_dataset([path])
        client = MasterClient(service=svc)

        x = layer.data("x", paddle.data_type.dense_vector(4))
        lbl = layer.data("lbl", paddle.data_type.integer_value(2))
        out = layer.fc(x, 2, act=paddle.activation.Softmax(), name="mf_out")
        cost = layer.classification_cost(out, lbl, name="mf_cost")
        params = paddle.parameters.create(cost, KeySource(0))
        tr = paddle.trainer.SGD(cost=cost, parameters=params,
                                update_equation=paddle.optimizer.Momentum(
                                    learning_rate=0.1))
        seen = []
        raw = client.reader(max_epochs=1)
        decoded = lambda: (pickle.loads(r) for r in raw())  # noqa: E731
        tr.train(reader=paddle.batch(decoded, 16), num_passes=1,
                 event_handler=lambda e: seen.append(e.cost) if isinstance(
                     e, paddle.event.EndIteration) else None)
        assert len(seen) == 4          # 64 records / bs 16
        assert svc.epoch() == 1

    def test_killed_consumer_work_is_redelivered(self, tmp_path):
        """Consumer A leases a task and dies (never reports); consumer B
        still streams every record after A's lease expires."""
        import pickle

        from paddle_tpu.runtime.master import MasterClient, MasterService

        path = self._write_recordio(tmp_path, n=32)
        clock = [0.0]
        svc = MasterService(lease_seconds=1.0, time_fn=lambda: clock[0])
        svc.set_dataset([path])

        # consumer A leases one task and is never heard from again
        a = MasterClient(service=svc)
        dead_task = a.get_task()
        assert dead_task is not None

        clock[0] += 2.0                # A's lease expires

        b = MasterClient(service=svc)
        got = []
        for rec in b.reader(max_epochs=1)():
            got.append(pickle.loads(rec))
        assert len(got) == 32          # including A's abandoned records
        assert svc.epoch() == 1


class TestElasticResume:
    """End-to-end preemption story (reference: the go/master task-lease +
    pserver-checkpoint combination, doc/design/cluster_train — any trainer
    can die; its task is redelivered; state resumes from checkpoints):
    trainer A checkpoints mid-stream and is preempted holding a task lease;
    trainer B resumes from A's checkpoint AND the master redelivers A's
    abandoned records."""

    def _build_trainer(self):
        import paddle_tpu as paddle
        from paddle_tpu import layer
        from paddle_tpu.utils.rng import KeySource

        x = layer.data("el_x", paddle.data_type.dense_vector(4))
        lbl = layer.data("el_l", paddle.data_type.integer_value(2))
        out = layer.fc(x, 2, act=paddle.activation.Softmax(), name="el_out")
        cost = layer.classification_cost(out, lbl, name="el_cost")
        params = paddle.parameters.create(cost, KeySource(21))
        return paddle.trainer.SGD(
            cost=cost, parameters=params,
            update_equation=paddle.optimizer.Momentum(learning_rate=0.05))

    def test_preempted_trainer_resumes_and_master_redelivers(self, tmp_path):
        import pickle

        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu.io import checkpoint as ckpt_io
        from paddle_tpu.runtime import recordio
        from paddle_tpu.runtime.master import MasterClient, MasterService

        rng = np.random.RandomState(3)
        path = str(tmp_path / "data.rio")
        with recordio.Writer(path, records_per_chunk=8) as w:
            for i in range(64):
                y = int(rng.randint(2))
                w.write(pickle.dumps(
                    ((rng.randn(4) + 2 * y).astype(np.float32), y)))

        clock = [0.0]
        svc = MasterService(lease_seconds=5.0, num_passes=1,
                            time_fn=lambda: clock[0])
        svc.set_dataset([path])
        ckdir = str(tmp_path / "ck")

        # trainer A: consumes 3 tasks, checkpoints, then is "preempted"
        # while holding a 4th lease it never finishes
        a_client = MasterClient(service=svc)
        tr_a = self._build_trainer()
        consumed = []
        for _ in range(3):
            task = a_client.get_task()
            recs = [pickle.loads(r) for off, _ in task.chunks
                    for r in recordio.read_chunk(task.path, off)]
            consumed.extend(recs)
            tr_a.train(reader=paddle.batch(lambda: iter(recs), 8),
                       num_passes=1, checkpoint_dir=ckdir)
            a_client.report_done(task.task_id, task.lease)
        abandoned = a_client.get_task()      # preempted holding this lease
        assert abandoned is not None
        step_a = tr_a._step
        assert ckpt_io.latest_checkpoint(ckdir) is not None

        clock[0] += 10.0                     # A's lease expires

        # trainer B: fresh object (fresh process equivalent) resumes from
        # A's checkpoint and streams every remaining record incl. A's
        # abandoned task
        b_client = MasterClient(service=svc)
        tr_b = self._build_trainer()
        remaining = []
        while True:
            task = b_client.get_task()
            if task is None:
                break
            recs = [pickle.loads(r) for off, _ in task.chunks
                    for r in recordio.read_chunk(task.path, off)]
            remaining.extend(recs)
            tr_b.train(reader=paddle.batch(lambda: iter(recs), 8),
                       num_passes=1, checkpoint_dir=ckdir)
            b_client.report_done(task.task_id, task.lease)

        assert tr_b._step > step_a           # resumed, not restarted
        assert len(consumed) + len(remaining) == 64   # no record lost
        assert svc.epoch() == 1


MASTER_REPLICA = textwrap.dedent("""
    import sys, time
    sys.path.insert(0, {repo!r})
    from paddle_tpu.runtime.master import HAMaster

    ha = HAMaster(lock_path={lock!r}, snapshot_path={snap!r},
                  stale_after=1.0, heartbeat_interval=0.2,
                  lease_seconds=5.0, num_passes=1, dataset=[{data!r}])
    assert ha.campaign(poll_interval=0.1)
    print("LEADER", ha.lock.term, flush=True)
    while True:
        time.sleep(0.5)
""")


class TestMasterFailover:
    """The master ITSELF dies (reference: go/master/etcd_client.go leader
    election + service.go state recovery): a standby replica adopts the
    snapshot, resumes serving, and a discovery-path client finishes the
    pass without losing a single record."""

    def test_killed_master_standby_takes_over(self, tmp_path):
        import pickle
        import signal
        import time

        import numpy as np

        from paddle_tpu.runtime import recordio
        from paddle_tpu.runtime.master import MasterClient

        path = str(tmp_path / "data.rio")
        rng = np.random.RandomState(0)
        with recordio.Writer(path, records_per_chunk=4) as w:
            for i in range(48):
                w.write(pickle.dumps((i, rng.rand(2).astype(np.float32))))

        lock = str(tmp_path / "leader.lock")
        snap = str(tmp_path / "master.snap")
        script = tmp_path / "replica.py"
        script.write_text(MASTER_REPLICA.format(
            repo=REPO, lock=lock, snap=snap, data=path))

        def spawn():
            return subprocess.Popen(
                [sys.executable, str(script)], stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)

        leader = spawn()
        standby = spawn()
        try:
            # wait for a leader to publish itself
            deadline = time.time() + 30
            while not os.path.exists(lock) and time.time() < deadline:
                time.sleep(0.1)
            assert os.path.exists(lock), "no leader elected"

            client = MasterClient(discovery_path=lock,
                                  failover_timeout=30.0)
            seen = []
            killed = False
            while True:
                task = client.get_task()
                if task is None:
                    st = client.status()
                    if st["epoch"] >= 1 or (st["todo"] == 0
                                            and st["pending"] == 0):
                        break
                    time.sleep(0.1)
                    continue
                for off, _ in task.chunks:
                    for rec in recordio.read_chunk(task.path, off):
                        seen.append(pickle.loads(rec)[0])
                client.report_done(task.task_id, task.lease)
                if not killed and len(seen) >= 12:
                    # kill the leader mid-pass (SIGKILL: no cleanup)
                    leader.kill()
                    leader.wait(timeout=10)
                    killed = True
            assert killed, "leader was never killed"
            # every record delivered at least once; repeats allowed only
            # for tasks in flight across the takeover (none here: the
            # client held no lease while the master died)
            assert set(seen) == set(range(48)), sorted(set(range(48))
                                                       - set(seen))
            client.close()
        finally:
            for p in (leader, standby):
                if p.poll() is None:
                    p.send_signal(signal.SIGKILL)
                p.wait(timeout=10)


TRANSFORMER_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    import paddle_tpu.distributed as dist
    dist.init()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models import transformer

    # hybrid mesh: dcn axis across the 2 processes, data x seq within —
    # a REAL model train step over the cluster (not just a psum):
    # ring-attention CP over seq, DP over data, grads psum'd over dcn
    mesh = dist.hybrid_mesh((2, 2), ("data", "seq"))
    assert dict(mesh.shape) == {{"dcn": 2, "data": 2, "seq": 2}}

    cfg = transformer.TransformerConfig(
        vocab=64, d_model=16, n_heads=2, n_layers=2, d_ff=32, max_len=16,
        dtype=jnp.float32, use_ring_attention=True)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, 64, (8, 16)).astype(np.int32))
    tgt = jnp.asarray(rng.randint(0, 64, (8, 16)).astype(np.int32))

    from jax.sharding import NamedSharding, PartitionSpec as P
    data_sh = NamedSharding(mesh, P(("dcn", "data"), None))
    toks = jax.device_put(toks, data_sh)
    tgt = jax.device_put(tgt, data_sh)
    params = jax.device_put(params, NamedSharding(mesh, P()))

    @jax.jit
    def train_step(p, tk, tg):
        loss, g = jax.value_and_grad(transformer.lm_loss)(
            p, tk, tg, cfg, mesh=mesh)
        return loss, jax.tree_util.tree_map(lambda w, gr: w - 0.1 * gr,
                                            p, g)

    l1, params = train_step(params, toks, tgt)
    l2, _ = train_step(params, toks, tgt)
    assert float(l2) < float(l1), (float(l1), float(l2))
    out_dir = os.environ["TEST_OUT_DIR"]
    rank = jax.process_index()
    with open(os.path.join(out_dir, f"tok_{{rank}}"), "w") as fh:
        fh.write(f"{{float(l1):.6f}} {{float(l2):.6f}}")
    print("transformer worker", rank, "OK", flush=True)
""")


@pytest.mark.slow
class TestMultiProcessTransformer:
    def test_two_process_transformer_train_step(self, tmp_path):
        """A full transformer LM train step (ring-attention CP x DP)
        spanning 2 processes x 4 virtual devices on a hybrid dcn mesh —
        the multi-host training capability, not just a collective."""
        _require_multiprocess_cpu()
        from paddle_tpu.runtime import launch

        worker = tmp_path / "tworker.py"
        worker.write_text(TRANSFORMER_WORKER.format(repo=REPO))
        rcs = launch.launch_local(
            2, [str(worker)], devices_per_proc=4,
            env_extra={"TEST_OUT_DIR": str(tmp_path)}, timeout=420)
        assert rcs == [0, 0], rcs
        # both processes observed the SAME (replicated) losses
        bodies = {(tmp_path / f"tok_{r}").read_text() for r in range(2)}
        assert len(bodies) == 1, bodies
