"""TrainJob end-to-end: epoch loop, history, checkpoint, callbacks,
dynamic parallelism, goal accuracy, stop."""

import os

import jax
import numpy as np
import pytest

from kubeml_tpu.api.errors import KubeMLException
from kubeml_tpu.api.types import TrainOptions, TrainRequest, TrainTask
from kubeml_tpu.data.registry import DatasetRegistry
from kubeml_tpu.models import get_builtin
from kubeml_tpu.models.base import KubeDataset
from kubeml_tpu.train.checkpoint import load_checkpoint
from kubeml_tpu.train.history import HistoryStore
from kubeml_tpu.train.job import JobCallbacks, TrainJob


class ToyDataset(KubeDataset):
    dataset = "blobs"


def make_blobs(reg, n_train=800, n_test=200, dim=8, classes=4, seed=0):
    """Linearly separable blobs: class c centered at one-hot(c)*3."""
    rng = np.random.RandomState(seed)

    def split(n):
        y = rng.randint(0, classes, n).astype(np.int32)
        # noisy enough that accuracy stays < 100% for a few epochs (the
        # default goal_accuracy=100 early-stop is reference parity)
        x = rng.randn(n, dim).astype(np.float32) * 2.0
        x[np.arange(n), y % dim] += 3.0
        return x, y

    xtr, ytr = split(n_train)
    xte, yte = split(n_test)
    return reg.create("blobs", xtr, ytr, xte, yte)


def make_task(job_id="testjob1", epochs=3, parallelism=2, k=2, batch=32,
              lr=0.1, static=True, validate_every=1, goal=100.0,
              engine="kavg"):
    req = TrainRequest(
        model_type="mlp", batch_size=batch, epochs=epochs, dataset="blobs",
        lr=lr, options=TrainOptions(
            default_parallelism=parallelism, static_parallelism=static,
            validate_every=validate_every, k=k, goal_accuracy=goal,
            engine=engine))
    return TrainTask(job_id=job_id, parameters=req, parallelism=parallelism)


@pytest.fixture()
def setup(tmp_path, tmp_home, mesh8):
    reg = DatasetRegistry()
    make_blobs(reg)
    store = HistoryStore()
    model = get_builtin("mlp")(hidden=16, num_classes=4)
    return reg, store, model, mesh8


def test_job_trains_and_persists(setup):
    reg, store, model, mesh = setup
    job = TrainJob(make_task(), model, ToyDataset(), mesh,
                   registry=reg, history_store=store)
    record = job.train()
    assert len(record.data.train_loss) == 3
    assert record.data.train_loss[-1] < record.data.train_loss[0]
    assert record.data.accuracy[-1] > 60.0
    assert record.data.parallelism == [2, 2, 2]
    # history persisted
    assert store.get("testjob1").data.accuracy == record.data.accuracy
    # checkpoint persisted and loadable
    variables, manifest = load_checkpoint("testjob1")
    assert manifest["model"] == "mlp"
    preds = model.infer(variables, np.zeros((4, 8), np.float32))
    assert preds.shape == (4,)


def test_goal_accuracy_early_stop(setup):
    reg, store, model, mesh = setup
    job = TrainJob(make_task(epochs=20, goal=50.0), model, ToyDataset(),
                   mesh, registry=reg, history_store=store)
    record = job.train()
    assert len(record.data.train_loss) < 20  # stopped early


def test_stop_signal(setup):
    reg, store, model, mesh = setup
    task = make_task(epochs=50)
    job = TrainJob(task, model, ToyDataset(), mesh, registry=reg,
                   history_store=store)
    calls = []

    def publish(m):
        calls.append(m)
        if len(calls) == 2:
            job.stop()

    job.callbacks = JobCallbacks(publish_metrics=publish)
    record = job.train()
    assert len(record.data.train_loss) == 2


def test_dynamic_parallelism_callback(setup):
    reg, store, model, mesh = setup
    asked = []

    def request_parallelism(task):
        asked.append(task.parallelism)
        return task.parallelism + 1  # scheduler scales up every epoch

    job = TrainJob(make_task(epochs=3, static=False), model, ToyDataset(),
                   mesh, registry=reg,
                   callbacks=JobCallbacks(request_parallelism=request_parallelism))
    record = job.train()
    assert record.data.parallelism == [2, 3, 4]
    assert asked == [2, 3]  # not asked after final epoch


def test_validate_every_cadence(setup):
    reg, store, model, mesh = setup
    job = TrainJob(make_task(epochs=4, validate_every=2), model,
                   ToyDataset(), mesh, registry=reg)
    record = job.train()
    acc = record.data.accuracy
    assert np.isnan(acc[0]) and not np.isnan(acc[1])
    assert not np.isnan(acc[3])


def test_failure_reports_exit_err(setup):
    reg, store, model, mesh = setup
    task = make_task()
    task.parameters.dataset = "missing"
    finished = []
    job = TrainJob(task, model, ToyDataset(), mesh, registry=reg,
                   callbacks=JobCallbacks(
                       on_finish=lambda jid, err: finished.append((jid, err))))
    with pytest.raises(Exception):
        job.train()
    assert finished and finished[0][1] is not None
    assert task.state == "failed"


def test_checkpoint_every_and_warm_start(setup, monkeypatch):
    reg, store, model, mesh = setup
    # epoch-cadence checkpointing: every epoch must produce a checkpoint
    # save in addition to the final one
    import kubeml_tpu.train.checkpoint as ckpt_mod
    saved = []
    real_save = ckpt_mod.save_checkpoint
    monkeypatch.setattr(
        ckpt_mod, "save_checkpoint",
        lambda jid, v, m, root=None: saved.append(m)
        or real_save(jid, v, m, root=root))
    task = make_task(job_id="ckptjob1", epochs=2)
    task.parameters.options.checkpoint_every = 1
    TrainJob(task, model, ToyDataset(), mesh, registry=reg,
             history_store=store).train()
    # saves are async latest-wins, so intermediate epochs may be elided
    # under write pressure; the durable contract is: at least one save
    # happened, the last one captured the final epoch, and the redundant
    # final save was skipped (the epoch-2 periodic save covers it)
    assert saved and saved[-1].get("epoch") == 2
    assert all(m.get("epoch") is not None for m in saved)
    variables, manifest = load_checkpoint("ckptjob1")
    assert manifest["function"] == "mlp"
    assert manifest["epoch"] == 2

    # warm start: the resumed job's first-epoch loss must be ~ the donor's
    # last loss, well below a cold start's first-epoch loss
    cold = TrainJob(make_task(job_id="coldjob1", epochs=1),
                    get_builtin("mlp")(hidden=16, num_classes=4),
                    ToyDataset(), mesh, registry=reg, history_store=store)
    cold_rec = cold.train()

    warm_task = make_task(job_id="warmjob1", epochs=1)
    warm_task.parameters.resume_from = "ckptjob1"
    warm = TrainJob(warm_task,
                    get_builtin("mlp")(hidden=16, num_classes=4),
                    ToyDataset(), mesh, registry=reg, history_store=store)
    warm_rec = warm.train()
    assert warm_rec.data.train_loss[0] < cold_rec.data.train_loss[0]


def test_resume_from_self_continues_job(setup):
    """Crash-recovery resume (resume_from == own job id): the job
    restores completed-epoch history, epoch index, and the negotiated
    parallelism from the mid-job checkpoint manifest, then runs ONLY
    the remaining epochs — one continuous history (the contract the PS
    watchdog's checkpoint-based restart builds on)."""
    from kubeml_tpu.train.checkpoint import save_checkpoint

    reg, store, model, mesh = setup
    first = TrainJob(make_task(job_id="resumejob1", epochs=2),
                     model, ToyDataset(), mesh, registry=reg,
                     history_store=store)
    rec1 = first.train()

    # re-publish the checkpoint as crash-time state: a mid-job manifest
    # claiming 2 epochs done and N=5 negotiated for the next epoch
    variables, manifest = load_checkpoint("resumejob1")
    crafted = dict(manifest, epoch=2, history=rec1.data.to_dict(),
                   parallelism=5)
    crafted.pop("completed", None)  # mid-job state, not a finished one
    save_checkpoint("resumejob1", variables, crafted)

    task = make_task(job_id="resumejob1", epochs=4)
    task.parameters.resume_from = "resumejob1"
    job2 = TrainJob(task, get_builtin("mlp")(hidden=16, num_classes=4),
                    ToyDataset(), mesh, registry=reg, history_store=store)
    rec2 = job2.train()

    assert job2._start_epoch == 2
    assert len(rec2.data.train_loss) == 4
    # restored epochs preserved verbatim; remaining epochs ran at the
    # manifest's carried-over parallelism, not the task default
    assert rec2.data.train_loss[:2] == rec1.data.train_loss
    assert rec2.data.parallelism == [2, 2, 5, 5]
    # training actually continued from the checkpoint weights
    assert rec2.data.train_loss[2] < rec1.data.train_loss[0]


def test_resume_from_self_completed_job_retrains_nothing(setup):
    """A process killed between its final checkpoint and its /finish
    notification leaves a manifest stamped completed=True; the restart
    must resume straight into completion — full history, zero epochs
    retrained — not rerun the job from its last epoch count."""
    import json
    import os

    from kubeml_tpu.api.const import kubeml_home

    reg, store, model, mesh = setup
    first = TrainJob(make_task(job_id="donejob1", epochs=2), model,
                     ToyDataset(), mesh, registry=reg, history_store=store)
    rec1 = first.train()
    with open(os.path.join(kubeml_home(), "models", "donejob1",
                           "manifest.json")) as f:
        assert json.load(f)["completed"] is True

    task = make_task(job_id="donejob1", epochs=2)
    task.parameters.resume_from = "donejob1"
    job2 = TrainJob(task, get_builtin("mlp")(hidden=16, num_classes=4),
                    ToyDataset(), mesh, registry=reg, history_store=store)
    rec2 = job2.train()
    assert job2._start_epoch == 2  # loop skipped entirely
    assert rec2.data.train_loss == rec1.data.train_loss
    assert rec2.data.accuracy == rec1.data.accuracy


def test_job_shuffle_option(setup):
    """options.shuffle reaches the RoundLoader (job path of the loader
    regression tests): epoch document order differs between epochs and
    the job still converges."""
    reg, store, model, mesh = setup
    task = make_task(job_id="shufjob1", epochs=2)
    task.parameters.options.shuffle = True
    job = TrainJob(task, model, ToyDataset(), mesh, registry=reg,
                   history_store=store)
    record = job.train()
    assert job._loader.shuffle is True
    assert len(record.data.train_loss) == 2
    assert record.data.train_loss[-1] < record.data.train_loss[0]
    # same job without shuffle keeps the parity default
    job2 = TrainJob(make_task(job_id="noshuf1", epochs=1), model,
                    ToyDataset(), mesh, registry=reg, history_store=store)
    job2.train()
    assert job2._loader.shuffle is False


def test_final_save_survives_periodic_failure(setup, monkeypatch):
    """A transient periodic-save failure with no later successful save
    must not abort the job: the final synchronous save is the
    remediation (ADVICE r1), and the published checkpoint holds the end
    state."""
    import kubeml_tpu.train.checkpoint as ckpt_mod
    reg, store, model, mesh = setup
    real_save = ckpt_mod.save_checkpoint
    calls = {"n": 0}

    def flaky(jid, v, m, root=None):
        calls["n"] += 1
        if m.get("epoch") is not None:  # every periodic save fails
            raise OSError("disk full")
        return real_save(jid, v, m, root=root)

    monkeypatch.setattr(ckpt_mod, "save_checkpoint", flaky)
    task = make_task(job_id="flakyckpt1", epochs=2)
    task.parameters.options.checkpoint_every = 2  # only the LAST epoch,
    # so no later periodic success supersedes the failure
    record = TrainJob(task, model, ToyDataset(), mesh, registry=reg,
                      history_store=store).train()
    assert len(record.data.train_loss) == 2
    variables, manifest = load_checkpoint("flakyckpt1")
    assert manifest["model"] == "mlp"
    # the final (sync) save won: only it stamps completed=True (periodic
    # saves never do — and all of them failed here anyway)
    assert manifest.get("completed") is True
    assert manifest.get("epoch") == 2
    # the periodic attempt ran (and failed) through the async writer;
    # the final save goes through job.py's direct import, unpatched
    assert calls["n"] >= 1


def test_warm_start_function_mismatch_rejected(setup):
    reg, store, model, mesh = setup
    donor = TrainJob(make_task(job_id="donor1", epochs=1), model,
                     ToyDataset(), mesh, registry=reg, history_store=store)
    donor.train()

    task = make_task(job_id="mismatch1", epochs=1)
    task.parameters.model_type = "lenet"
    task.parameters.resume_from = "donor1"
    bad = TrainJob(task, get_builtin("lenet")(), ToyDataset(), mesh,
                   registry=reg, history_store=store)
    with pytest.raises(Exception, match="holds function"):
        bad.train()


def test_straggler_tolerance_under_fault_injection(setup):
    """Random worker loss every round: the job must finish, learn, and
    average only over survivors (reference semantics util.go:144-166)."""
    from kubeml_tpu.utils.chaos import WorkerLossInjector

    reg, store, model, mesh = setup
    chaos = WorkerLossInjector(p=0.4, seed=7)
    job = TrainJob(make_task(job_id="chaosjob1", epochs=3, parallelism=4),
                   model, ToyDataset(), mesh, registry=reg,
                   history_store=store, round_hook=chaos)
    record = job.train()
    assert chaos.degraded_rounds > 0 and chaos.workers_lost > 0
    assert len(record.data.train_loss) == 3
    assert record.data.train_loss[-1] < record.data.train_loss[0]
    assert np.isfinite(record.data.train_loss).all()
    assert record.data.accuracy[-1] > 50.0


def test_syncdp_engine_job(setup):
    """options.engine='syncdp' trains through the product path: per-step
    gradient averaging, persistent optimizer state, same history/
    checkpoint/validate surface as kavg."""
    reg, store, model, mesh = setup
    job = TrainJob(make_task(job_id="syncjob1", engine="syncdp", lr=0.05),
                   model, ToyDataset(), mesh, registry=reg,
                   history_store=store)
    record = job.train()
    assert len(record.data.train_loss) == 3
    assert record.data.train_loss[-1] < record.data.train_loss[0]
    assert np.isfinite(record.data.train_loss).all()
    assert record.data.accuracy[-1] > 60.0
    # checkpoint works off the syncdp state's variables view
    variables, manifest = load_checkpoint("syncjob1")
    preds = model.infer(variables, np.zeros((4, 8), np.float32))
    assert preds.shape == (4,)


def test_syncdp_straggler_tolerance(setup):
    """Worker loss under syncdp: the lost worker's samples drop out of
    the global batch (mask), the job still finishes and learns."""
    from kubeml_tpu.utils.chaos import WorkerLossInjector

    reg, store, model, mesh = setup
    chaos = WorkerLossInjector(p=0.4, seed=7)
    job = TrainJob(make_task(job_id="syncchaos1", epochs=3, parallelism=4,
                             engine="syncdp", lr=0.05),
                   model, ToyDataset(), mesh, registry=reg,
                   history_store=store, round_hook=chaos)
    record = job.train()
    assert chaos.degraded_rounds > 0 and chaos.workers_lost > 0
    assert record.data.train_loss[-1] < record.data.train_loss[0]
    assert np.isfinite(record.data.train_loss).all()


def test_unknown_engine_rejected(setup):
    reg, store, model, mesh = setup
    job = TrainJob(make_task(job_id="badengine1", engine="sgd"),
                   model, ToyDataset(), mesh, registry=reg,
                   history_store=store)
    with pytest.raises(Exception, match="unknown training engine"):
        job.train()


def test_all_workers_lost_aborts(setup):
    """Zero survivors in a round is the job-abort path (job.go:188-193)."""
    reg, store, model, mesh = setup

    def kill_all(rb):
        import dataclasses as dc
        return dc.replace(rb, worker_mask=np.zeros_like(rb.worker_mask))

    job = TrainJob(make_task(job_id="deadjob1", epochs=2), model,
                   ToyDataset(), mesh, registry=reg, history_store=store,
                   round_hook=kill_all)
    with pytest.raises(Exception, match="no workers contributed"):
        job.train()
    assert job.exit_err is not None


# ------------------------------------------- job-level TP / SP (net-new)


def make_token_task(reg, name="toktask", n_train=256, n_test=64, T=16,
                    vocab=1000, seed=0):
    """Learnable text classification: label = first token > vocab/2."""
    rng = np.random.RandomState(seed)

    def split(n):
        x = rng.randint(1, vocab, size=(n, T)).astype(np.int32)
        y = (x[:, 0] > vocab // 2).astype(np.int32)
        return x, y

    xtr, ytr = split(n_train)
    xte, yte = split(n_test)
    return reg.create(name, xtr, ytr, xte, yte)


class TokenDataset(KubeDataset):
    dataset = "toktask"


def test_job_tensor_parallel_bert(tmp_home, mesh8):
    """A DP x TP job: --tensor-parallel 2 carves the 8-device mesh into
    data=4 x model=2, Megatron-shards the variables, trains AND
    validates (VERDICT r1 item 3's done criterion at the job layer)."""
    from kubeml_tpu.parallel.mesh import MODEL_AXIS, data_axis_size

    reg = DatasetRegistry()
    make_token_task(reg)
    store = HistoryStore()
    model = get_builtin("bert-tiny")()
    task = make_task(job_id="tpjob1", epochs=2, parallelism=4, k=1,
                     batch=16, lr=1e-3)
    task.parameters.model_type = "bert-tiny"
    task.parameters.dataset = "toktask"
    task.parameters.options.n_model = 2
    job = TrainJob(task, model, TokenDataset(), mesh8, registry=reg,
                   history_store=store)
    record = job.train()
    assert data_axis_size(job.mesh) == 4
    assert job.mesh.shape[MODEL_AXIS] == 2
    # variables actually carry model-axis shardings
    specs = [v.sharding.spec for v in
             jax.tree_util.tree_leaves(job.variables)
             if hasattr(v, "sharding")]
    assert any(MODEL_AXIS in str(s) for s in specs)
    assert record.data.train_loss[-1] < record.data.train_loss[0]
    assert record.data.accuracy[-1] == record.data.accuracy[-1]  # validated


def test_job_seq_parallel_gpt(tmp_home, mesh8):
    """A DP x SP job: --seq-parallel 2 trains the causal LM with ring
    attention inside the engine round; loss falls and validation runs
    (VERDICT r1 item 4 at the job layer)."""
    from kubeml_tpu.parallel.mesh import SEQ_AXIS, data_axis_size
    from tests.test_models_gpt import TinyGPT

    class LMDataset(KubeDataset):
        dataset = "lmtask"

        def transform_train(self, data, labels):
            return {"x": data}

        transform_test = transform_train

    reg = DatasetRegistry()
    rng = np.random.RandomState(0)

    def lm_split(n, T=32):
        start = rng.randint(1, 63, size=(n, 1))
        seq = (start + np.arange(T)[None, :] - 1) % 63 + 1
        return seq.astype(np.int32), np.zeros(n, np.int32)

    xtr, ytr = lm_split(256)
    xte, yte = lm_split(64)
    reg.create("lmtask", xtr, ytr, xte, yte)

    store = HistoryStore()
    task = make_task(job_id="spjob1", epochs=2, parallelism=4, k=1,
                     batch=16, lr=3e-3)
    task.parameters.model_type = "gpt-mini"
    task.parameters.dataset = "lmtask"
    task.parameters.options.n_seq = 2
    job = TrainJob(task, TinyGPT(), LMDataset(), mesh8, registry=reg,
                   history_store=store)
    record = job.train()
    assert data_axis_size(job.mesh) == 4
    assert job.mesh.shape[SEQ_AXIS] == 2
    assert job.model.module.seq_axis == SEQ_AXIS
    assert record.data.train_loss[-1] < record.data.train_loss[0]
    assert record.data.accuracy[-1] == record.data.accuracy[-1]


def test_job_seq_and_expert_parallel_moe(tmp_home, mesh8):
    """SP x EP at the job surface (round 4, the matrix's last
    exclusion): --seq-parallel 2 --expert-parallel 2 carves
    data=2 x seq=2 x expert=2 and trains the MoE trunk with experts
    sharded over the expert axis inside the fully-manual round — the
    vma backward assembles the expert-weight gradients exactly as it
    does manual TP's."""
    from kubeml_tpu.parallel.mesh import (EXPERT_AXIS, SEQ_AXIS,
                                          data_axis_size)

    class LMDataset(KubeDataset):
        dataset = "lmtask"

        def transform_train(self, data, labels):
            return {"x": data}

        transform_test = transform_train

    reg = DatasetRegistry()
    rng = np.random.RandomState(0)

    def lm_split(n, T=32):
        start = rng.randint(1, 63, size=(n, 1))
        seq = (start + np.arange(T)[None, :] - 1) % 63 + 1
        return seq.astype(np.int32), np.zeros(n, np.int32)

    xtr, ytr = lm_split(256)
    xte, yte = lm_split(64)
    reg.create("lmtask", xtr, ytr, xte, yte)

    from tests.test_models_gpt import TinyMoE

    store = HistoryStore()
    task = make_task(job_id="spepjob1", epochs=2, parallelism=2, k=1,
                     batch=16, lr=3e-3)
    task.parameters.model_type = "gpt-moe-mini"
    task.parameters.dataset = "lmtask"
    task.parameters.options.n_seq = 2
    task.parameters.options.n_expert = 2
    job = TrainJob(task, TinyMoE(), LMDataset(), mesh8, registry=reg,
                   history_store=store)
    record = job.train()
    assert data_axis_size(job.mesh) == 2
    assert job.mesh.shape[SEQ_AXIS] == 2
    assert job.mesh.shape[EXPERT_AXIS] == 2
    assert job.model.module.seq_axis == SEQ_AXIS
    assert job.model.module.ep_axis == EXPERT_AXIS
    assert record.data.train_loss[-1] < record.data.train_loss[0]
    assert record.data.accuracy[-1] == record.data.accuracy[-1]


def test_job_expert_parallel_alone_rejects_non_moe(tmp_home, mesh8):
    """Round 5 lifts the EP-requires-SP restriction: --expert-parallel
    alone now routes to the GSPMD ep_mesh path, so a function without
    experts gets the model-surface rejection (as a 400), not a
    requires-seq-parallel error."""
    reg = DatasetRegistry()
    make_blobs(reg)
    task = make_task(job_id="eponly1", epochs=1)
    task.parameters.options.n_expert = 2
    job = TrainJob(task, get_builtin("mlp")(hidden=16, num_classes=4),
                   ToyDataset(), mesh8, registry=reg)
    with pytest.raises(KubeMLException, match="no experts to shard") as ei:
        job.train()
    assert ei.value.status_code == 400


def test_job_expert_parallel_rejects_non_moe(tmp_home, mesh8):
    """--expert-parallel on a function without experts fails with the
    model-surface message, not a trace-time explosion."""
    from tests.test_models_gpt import TinyGPT

    class LMDataset(KubeDataset):
        dataset = "lmtask2"

        def transform_train(self, data, labels):
            return {"x": data}

        transform_test = transform_train

    reg = DatasetRegistry()
    rng = np.random.RandomState(0)
    x = rng.randint(1, 63, size=(64, 32)).astype(np.int32)
    reg.create("lmtask2", x, np.zeros(64, np.int32), x[:16],
               np.zeros(16, np.int32))
    task = make_task(job_id="epbad1", epochs=1, parallelism=2, k=1,
                     batch=16)
    task.parameters.model_type = "gpt-mini"
    task.parameters.dataset = "lmtask2"
    task.parameters.options.n_seq = 2
    task.parameters.options.n_expert = 2
    job = TrainJob(task, TinyGPT(), LMDataset(), mesh8, registry=reg)
    with pytest.raises(KubeMLException, match="no experts to shard"):
        job.train()


def test_job_tensor_and_seq_parallel_combined(tmp_home, mesh8):
    """Round 2's exclusion cleared at the job surface: --tensor-parallel 2
    --seq-parallel 2 carves data=2 x model=2 x seq=2 and trains the
    fully-manual round (Megatron psums + KV ring in one program)."""
    from kubeml_tpu.parallel.mesh import (MODEL_AXIS, SEQ_AXIS,
                                          data_axis_size)

    reg = DatasetRegistry()
    make_token_task(reg)
    store = HistoryStore()
    model = get_builtin("bert-tiny")()
    task = make_task(job_id="tpspjob1", epochs=2, parallelism=2, k=1,
                     batch=16, lr=1e-3)
    task.parameters.model_type = "bert-tiny"
    task.parameters.dataset = "toktask"
    task.parameters.options.n_model = 2
    task.parameters.options.n_seq = 2
    job = TrainJob(task, model, TokenDataset(), mesh8, registry=reg,
                   history_store=store)
    record = job.train()
    assert data_axis_size(job.mesh) == 2
    assert job.mesh.shape[MODEL_AXIS] == 2
    assert job.mesh.shape[SEQ_AXIS] == 2
    assert job.model.module.tp_axis == MODEL_AXIS
    assert job.model.module.seq_axis == SEQ_AXIS
    assert record.data.train_loss[-1] < record.data.train_loss[0]
    assert record.data.accuracy[-1] == record.data.accuracy[-1]  # validated


def test_job_parallelism_option_validation(setup):
    """Clear 400s for every unsupported TP/SP combination."""
    from kubeml_tpu.api.errors import KubeMLException
    reg, store, model, mesh = setup

    def expect_400(mutate, m=None, match=""):
        task = make_task(job_id="badopt1", epochs=1)
        mutate(task.parameters.options)
        job = TrainJob(task, m or get_builtin("mlp")(hidden=16,
                                                     num_classes=4),
                       ToyDataset(), mesh, registry=reg,
                       history_store=store)
        with pytest.raises(KubeMLException) as ei:
            job.train()
        assert ei.value.status_code == 400
        assert match in str(ei.value.message)

    # TP on a model with no rules
    expect_400(lambda o: setattr(o, "n_model", 2), match="tensor-parallel")
    # manual TP on a model without a tp_axis module
    def manual_on_mlp(o):
        o.n_model = 2
        o.tp_impl = "manual"
    expect_400(manual_on_mlp, match="manual tensor parallelism")
    # manual TP on MoE: curated 400, not a trace-time 500 (the module
    # HAS a tp_axis field but the expert FFNs reject the split)
    expect_400(manual_on_mlp, m=get_builtin("gpt-moe-mini")(),
               match="expert")
    # TP + SP combined runs manual TP, which requires ring (not ulysses)
    def both_ulysses(o):
        o.n_model = 2
        o.n_seq = 2
        o.seq_impl = "ulysses"
    expect_400(both_ulysses, m=get_builtin("bert-tiny")(), match="ring")
    # unknown tp_impl
    def bad_impl(o):
        o.n_model = 2
        o.tp_impl = "magic"
    expect_400(bad_impl, m=get_builtin("bert-tiny")(), match="tp_impl")
    # syncdp + TP
    def sync_tp(o):
        o.engine = "syncdp"
        o.n_model = 2
    expect_400(sync_tp, m=get_builtin("bert-tiny")(), match="kavg")
    # indivisible device count: 8 devices, factor 3
    expect_400(lambda o: setattr(o, "n_model", 3),
               m=get_builtin("bert-tiny")(), match="divisible")
    # SP on a model with no seq support
    expect_400(lambda o: setattr(o, "n_seq", 2), match="sequence")


def test_max_parallelism_caps_scheduler_growth(setup):
    """options.max_parallelism stops the reference policy's unbounded
    worker accretion (policy.go:75-90 floor-clamps at 1 only), binds
    from epoch 1, and rejects negative values."""
    from kubeml_tpu.api.errors import KubeMLException
    reg, store, model, mesh = setup
    task = make_task(job_id="capjob1", epochs=4, static=False)
    task.parameters.options.max_parallelism = 3

    job = TrainJob(task, model, ToyDataset(), mesh, registry=reg,
                   callbacks=JobCallbacks(
                       request_parallelism=lambda t: t.parallelism + 1))
    record = job.train()
    assert record.data.parallelism == [2, 3, 3, 3]

    # the cap binds on the INITIAL parallelism too
    over = make_task(job_id="capjob2", epochs=1, parallelism=8)
    over.parameters.options.max_parallelism = 3
    rec2 = TrainJob(over, model, ToyDataset(), mesh,
                    registry=reg).train()
    assert rec2.data.parallelism == [3]

    bad = make_task(job_id="capjob3", epochs=1)
    bad.parameters.options.max_parallelism = -2
    with pytest.raises(KubeMLException) as ei:
        TrainJob(bad, model, ToyDataset(), mesh, registry=reg).train()
    assert ei.value.status_code == 400


def test_elastic_shape_pinning_single_program(setup):
    """Recompile-free elastic N: with a max_parallelism cap, every ±1
    the policy takes reuses ONE compiled round program (W pinned at the
    lane-padded cap, N expressed through the worker mask) and ONE eval
    program — the fix for the 20-200 s per-±1 recompiles that dominated
    the round-4 autoscale trajectories."""
    from kubeml_tpu.parallel.mesh import make_mesh
    reg, store, model, _ = setup
    # a 1-lane mesh so lane padding can't mask the effect: without the
    # pin, W would track N exactly and every ±1 would be a new program
    mesh1 = make_mesh(n_data=1, devices=jax.devices()[:1])
    schedule = iter([3, 4, 3, 2, 4])
    task = make_task(job_id="elastic1", epochs=6, static=False)
    task.parameters.options.max_parallelism = 4
    job = TrainJob(task, model, ToyDataset(), mesh1, registry=reg,
                   history_store=store,
                   callbacks=JobCallbacks(
                       request_parallelism=lambda t: next(schedule, None)))
    record = job.train()
    assert record.data.parallelism == [2, 3, 4, 3, 2, 4]
    # one train program, one eval program — across FIVE parallelism moves
    assert len(job._engine._train_cache) == 1
    assert len(job._engine._eval_cache) == 1
    # pinned W is the lane-padded cap; training still converges
    assert job._loader.w_floor == 4
    assert record.data.accuracy[-1] > 60.0


def test_elastic_uncapped_grow_only_shapes(setup):
    """Without a cap, W is a grow-only high-water mark: scale-downs
    never reshape (no recompile), only crossing a new maximum does."""
    from kubeml_tpu.parallel.mesh import make_mesh
    reg, store, model, _ = setup
    mesh1 = make_mesh(n_data=1, devices=jax.devices()[:1])
    schedule = iter([4, 2, 4, 3])
    task = make_task(job_id="elastic2", epochs=5, static=False)
    job = TrainJob(task, model, ToyDataset(), mesh1, registry=reg,
                   callbacks=JobCallbacks(
                       request_parallelism=lambda t: next(schedule, None)))
    record = job.train()
    assert record.data.parallelism == [2, 4, 2, 4, 3]
    # two shapes ever: W=2 (start) and W=4 (first growth); the 4->2->4
    # moves reuse the W=4 program
    assert len(job._engine._train_cache) == 2
    assert job._loader.w_floor == 4


def test_policy_elapsed_excludes_compile(setup):
    """The duration reported to the throughput policy subtracts compile
    spikes (RoundStats.compiled), falling back to the cross-epoch EMA
    when every round of an epoch compiled (1-round epochs)."""
    reg, store, model, mesh = setup
    job = TrainJob(make_task(), model, ToyDataset(), mesh, registry=reg)
    # epoch 1: no steady sample yet — a steady dispatch is ~0 (async
    # dispatch is ms), so the whole spike counts as compile; otherwise
    # the policy's prev==0.0 branch would record a compile-inflated
    # reference time and grant every later epoch a spurious +1
    job._note_round_times([(5.0, 1, True, "kavg.train")])
    assert job._compile_overhead_s == 5.0
    # steady dispatches establish the EMA, normalized PER ROUND first:
    # a 2-round grouped dispatch at 0.04s is a 0.02s/round sample
    job._note_round_times([(0.04, 2, False, "kavg.train_multi"),
                           (0.04, 1, False, "kavg.train")])
    assert job._compile_overhead_s == 0.0
    assert abs(job._steady_round_ema - 0.03) < 1e-9
    # mixed epoch: spike minus the would-have-been steady cost of the
    # ROUNDS the compiling dispatch carried (2 here)
    job._note_round_times([(4.0, 2, True, "kavg.train_multi"),
                           (0.03, 1, False, "kavg.train")])
    assert abs(job._compile_overhead_s - (4.0 - 2 * 0.03)) < 1e-6
    # all-compiled epoch: the EMA stands in for the steady estimate
    job._note_round_times([(2.0, 1, True, "kavg.train")])
    assert abs(job._compile_overhead_s - (2.0 - job._steady_round_ema)) \
        < 1e-6


def test_loader_shape_floors(setup):
    """RoundLoader w_floor/s_floor semantics: pinned W, grow-only
    high-water, and S tracking N for sparse averaging (k=-1)."""
    from kubeml_tpu.data.loader import RoundLoader
    reg, store, model, mesh = setup
    handle = reg.get("blobs")
    ld = RoundLoader(handle, ToyDataset(), n_lanes=1, w_floor=8)
    rb = next(iter(ld.epoch_rounds(ld.plan(2, 2, 32), epoch=0)))
    assert rb.batch["x"].shape[0] == 8          # W pinned at the floor
    assert rb.worker_mask.sum() == 2            # N through the mask only
    s_at_k2 = rb.batch["x"].shape[1]
    # a later smaller plan keeps the shape (grow-only)
    rb2 = next(iter(ld.epoch_rounds(ld.plan(1, 2, 32), epoch=1)))
    assert rb2.batch["x"].shape[:2] == (8, s_at_k2)
    # sparse averaging: S tracks N (no high-water) so the pinned shape
    # never pays whole-shard masked compute at the cap
    ld2 = RoundLoader(handle, ToyDataset(), n_lanes=1, w_floor=4)
    s1 = next(iter(ld2.epoch_rounds(ld2.plan(1, -1, 32),
                                    epoch=0))).batch["x"].shape[1]
    s4 = next(iter(ld2.epoch_rounds(ld2.plan(4, -1, 32),
                                    epoch=1))).batch["x"].shape[1]
    assert s4 < s1


def _lm_registry(name="pplm", n_train=128, n_test=32, T=16, seed=0):
    """Tiny learnable LM dataset (ascending token runs) + its dataset
    class, for the pipeline/expert job-surface tests."""
    class LMDataset(KubeDataset):
        dataset = name

        def transform_train(self, data, labels):
            return {"x": data}

        transform_test = transform_train

    reg = DatasetRegistry()
    rng = np.random.RandomState(seed)

    def split(n):
        start = rng.randint(1, 63, size=(n, 1))
        seq = (start + np.arange(T)[None, :] - 1) % 63 + 1
        return seq.astype(np.int32), np.zeros(n, np.int32)

    if name not in [d.name for d in reg.list()]:
        reg.create(name, *split(n_train), *split(n_test))
    return reg, LMDataset()


def test_job_pipeline_parallel_matches_dense(tmp_home):
    """--pipeline-parallel at the job surface (round 5): data=4 x
    stage=2 trains the GPT trunk through the GPipe body inside the
    fully-manual round, and the merged history MATCHES the unpipelined
    job on an equal-lane mesh (same seed, same plan, dropout 0) —
    GPipe through the TrainJob is semantics-preserving, not just
    convergent."""
    import jax as _jax

    from kubeml_tpu.parallel.mesh import (STAGE_AXIS, data_axis_size,
                                          make_mesh)
    from tests.test_models_gpt import TinyGPT

    def run(n_stage, job_id):
        reg, ds = _lm_registry()
        task = make_task(job_id=job_id, epochs=2, parallelism=2, k=1,
                         batch=8, lr=3e-3)
        task.parameters.model_type = "gpt-mini"
        task.parameters.dataset = "pplm"
        task.parameters.options.n_stage = n_stage
        mesh = make_mesh(n_data=4, n_stage=n_stage)
        job = TrainJob(task, TinyGPT(), ds, mesh, registry=reg)
        return job, job.train()

    pp_job, pp_rec = run(2, "ppjob1")
    assert data_axis_size(pp_job.mesh) == 4
    assert pp_job.mesh.shape[STAGE_AXIS] == 2
    assert pp_job.model._pp_microbatches == 4  # auto: 2 x stages
    dense_job, dense_rec = run(1, "ppjob2")
    # TinyGPT is dropout-0 and the plans/rngs are seed-identical, so
    # the two jobs differ only by pipelined vs dense trunk execution
    np.testing.assert_allclose(pp_rec.data.train_loss,
                               dense_rec.data.train_loss,
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(pp_rec.data.accuracy,
                               dense_rec.data.accuracy,
                               rtol=2e-2, atol=0.5)
    assert pp_rec.data.train_loss[-1] < pp_rec.data.train_loss[0]


def test_job_pipeline_parallel_with_experts(tmp_home, mesh8):
    """PP x EP at the job surface: --pipeline-parallel 2
    --expert-parallel 2 carves data=2 x stage=2 x expert=2; the MoE
    trunk pipelines with experts sharded over the expert axis
    (ep_partial_ffn inside the same manual round)."""
    from kubeml_tpu.parallel.mesh import (EXPERT_AXIS, STAGE_AXIS,
                                          data_axis_size)
    from tests.test_models_gpt import TinyMoE

    reg, ds = _lm_registry()
    task = make_task(job_id="ppepjob1", epochs=2, parallelism=2, k=1,
                     batch=8, lr=3e-3)
    task.parameters.model_type = "gpt-moe-mini"
    task.parameters.dataset = "pplm"
    task.parameters.options.n_stage = 2
    task.parameters.options.n_expert = 2
    job = TrainJob(task, TinyMoE(), ds, mesh8, registry=reg)
    record = job.train()
    assert data_axis_size(job.mesh) == 2
    assert job.mesh.shape[STAGE_AXIS] == 2
    assert job.mesh.shape[EXPERT_AXIS] == 2
    assert job.model.module.ep_axis == EXPERT_AXIS
    assert record.data.train_loss[-1] < record.data.train_loss[0]


def test_job_dp_ep_gspmd_matches_replicated(tmp_home):
    """Plain DP x EP (round 5, no SP/PP required): --expert-parallel 2
    alone takes the GSPMD ep_mesh route — inner axes stay Auto and XLA
    materializes the token all-to-alls inside each DP lane — and the
    history matches the replicated-expert job on an equal-lane mesh."""
    from kubeml_tpu.parallel.mesh import (EXPERT_AXIS, data_axis_size,
                                          make_mesh)
    from tests.test_models_gpt import TinyMoE

    def run(n_expert, job_id):
        reg, ds = _lm_registry()
        task = make_task(job_id=job_id, epochs=2, parallelism=2, k=1,
                         batch=8, lr=3e-3)
        task.parameters.model_type = "gpt-moe-mini"
        task.parameters.dataset = "pplm"
        task.parameters.options.n_expert = n_expert
        mesh = make_mesh(n_data=4, n_expert=n_expert)
        job = TrainJob(task, TinyMoE(), ds, mesh, registry=reg)
        return job, job.train()

    ep_job, ep_rec = run(2, "dpepjob1")
    assert data_axis_size(ep_job.mesh) == 4
    assert ep_job.mesh.shape[EXPERT_AXIS] == 2
    assert ep_job.model.module.ep_mesh is ep_job.mesh
    _, dense_rec = run(1, "dpepjob2")
    np.testing.assert_allclose(ep_rec.data.train_loss,
                               dense_rec.data.train_loss,
                               rtol=2e-3, atol=2e-3)
    assert ep_rec.data.train_loss[-1] < ep_rec.data.train_loss[0]


def test_job_pipeline_parallel_misconfigs(tmp_home, mesh8):
    """PP misconfigs fail as 400s at the job surface, not trace-time
    explosions: unsupported family, SP/TP composition, indivisible
    microbatches, indivisible layers."""
    from tests.test_models_gpt import TinyGPT

    def expect_400(mutate, model=None, dataset=None, match=""):
        reg, ds = _lm_registry()
        if model is None:
            make_blobs(reg)
            model, ds = get_builtin("mlp")(hidden=16, num_classes=4), \
                ToyDataset()
            dsname = "blobs"
        else:
            dsname = "pplm"
        task = make_task(job_id="ppbad", epochs=1, parallelism=2, k=1,
                         batch=8)
        task.parameters.dataset = dsname
        mutate(task.parameters.options, task.parameters)
        job = TrainJob(task, model, ds or dataset, mesh8, registry=reg)
        with pytest.raises(KubeMLException, match=match) as ei:
            job.train()
        assert ei.value.status_code == 400

    # family without a pipelineable trunk
    expect_400(lambda o, r: setattr(o, "n_stage", 2),
               match="does not support pipeline")
    # PP + SP rejected up front
    def pp_sp(o, r):
        o.n_stage = 2
        o.n_seq = 2
    expect_400(pp_sp, model=TinyGPT(), match="composes with")
    # microbatches must divide the batch
    def bad_mb(o, r):
        o.n_stage = 2
        o.pp_microbatches = 3
    expect_400(bad_mb, model=TinyGPT(), match="microbatches")
    # layers must split over the stage axis (TinyGPT has 2 layers)
    def bad_layers(o, r):
        o.n_stage = 4
    expect_400(bad_layers, model=TinyGPT(), match="layers")
    # syncdp cannot host the manual pipeline round
    def pp_sync(o, r):
        o.n_stage = 2
        o.engine = "syncdp"
    expect_400(pp_sync, model=TinyGPT(), match="kavg")


def test_job_rounds_per_dispatch_matches_ungrouped(setup):
    """--rounds-per-dispatch R trains IDENTICALLY to per-round dispatch
    (merges preserved between rounds; tail rounds dispatch singly) —
    the option exists to amortize submission overhead, never to change
    math."""
    reg, store, model, mesh = setup

    def run(job_id, rpd):
        task = make_task(job_id=job_id, epochs=2, parallelism=3, k=2,
                         batch=32)
        task.parameters.options.rounds_per_dispatch = rpd
        m = get_builtin("mlp")(hidden=16, num_classes=4)
        job = TrainJob(task, m, ToyDataset(), mesh, registry=reg)
        return job.train()

    # parallelism 3 on 800 samples / b32 / k2: several rounds per epoch
    # with a non-multiple tail for the grouped arm
    plain = run("rpd1", 1)
    grouped = run("rpd2", 3)
    np.testing.assert_allclose(grouped.data.train_loss,
                               plain.data.train_loss, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(grouped.data.accuracy, plain.data.accuracy,
                               rtol=1e-5, atol=1e-5)


def test_job_fsdp_matches_replicated_syncdp(setup):
    """--fsdp (ZeRO-3) at the job surface: parameters + optimizer state
    shard over the data axis inside the syncdp engine, and the history
    MATCHES the replicated-parameter syncdp job — FSDP is a layout, not
    a math change. kavg + fsdp rejects as 400 (weight-average semantics
    preclude parameter sharding)."""
    reg, store, model, mesh = setup

    def run(job_id, fsdp):
        task = make_task(job_id=job_id, epochs=2, engine="syncdp",
                         lr=0.05)
        task.parameters.options.fsdp = fsdp
        m = get_builtin("mlp")(hidden=16, num_classes=4)
        job = TrainJob(task, m, ToyDataset(), mesh, registry=reg)
        return job, job.train()

    job, rec = run("fsdpjob1", True)
    # the params really live sharded: dim-0-divisible leaves carry a
    # data-axis sharding in the engine state
    import jax as _jax
    from jax.sharding import PartitionSpec as _P
    sharded = [
        l for l in _jax.tree_util.tree_leaves(
            job._sync_state["params"])
        if hasattr(l, "sharding")
        and l.sharding.spec == _P("data")]
    assert sharded, "no parameter leaf is data-sharded under fsdp"
    _, rec0 = run("fsdpjob0", False)
    np.testing.assert_allclose(rec.data.train_loss, rec0.data.train_loss,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(rec.data.accuracy, rec0.data.accuracy,
                               rtol=1e-5, atol=1e-5)

    bad = make_task(job_id="fsdpbad1", epochs=1)  # kavg engine
    bad.parameters.options.fsdp = True
    with pytest.raises(KubeMLException, match="syncdp") as ei:
        TrainJob(bad, get_builtin("mlp")(hidden=16, num_classes=4),
                 ToyDataset(), mesh, registry=reg).train()
    assert ei.value.status_code == 400


def test_job_pipeline_parallel_bert_matches_dense(tmp_home):
    """--pipeline-parallel on the BERT family (round 5 extension): the
    encoder trunk pipelines through the job and the history matches the
    unpipelined job on an equal-lane mesh."""
    from kubeml_tpu.models.bert import BertModule, BertTiny
    from kubeml_tpu.parallel.mesh import STAGE_AXIS, make_mesh

    class TinyBert(BertTiny):
        num_classes = 2

        def build(self):
            return BertModule(vocab_size=1000, max_len=16, hidden=32,
                              layers=2, heads=2, ffn=64, dropout=0.0,
                              num_classes=2)

    def run(n_stage, job_id):
        reg = DatasetRegistry()
        if "toktask" not in [d.name for d in reg.list()]:
            make_token_task(reg)
        task = make_task(job_id=job_id, epochs=2, parallelism=2, k=1,
                         batch=8, lr=1e-3)
        task.parameters.model_type = "bert-tiny"
        task.parameters.dataset = "toktask"
        task.parameters.options.n_stage = n_stage
        mesh = make_mesh(n_data=4, n_stage=n_stage)
        job = TrainJob(task, TinyBert(), TokenDataset(), mesh,
                       registry=reg)
        return job, job.train()

    pp_job, pp_rec = run(2, "bertpp1")
    assert pp_job.mesh.shape[STAGE_AXIS] == 2
    _, dense_rec = run(1, "bertpp2")
    np.testing.assert_allclose(pp_rec.data.train_loss,
                               dense_rec.data.train_loss,
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(pp_rec.data.accuracy,
                               dense_rec.data.accuracy,
                               rtol=2e-2, atol=0.5)


@pytest.mark.parametrize("case", ["env_set", "env_unset", "opted_out"])
def test_enable_compile_cache_placement(monkeypatch, tmp_path, case):
    """One compile cache, placeable from outside: with
    JAX_COMPILATION_CACHE_DIR set the directory JAX already holds is
    left untouched; unset, the cache goes to the FIXED in-checkout path
    (never $KUBEML_TPU_HOME, a temp dir, a pid or a time);
    KUBEML_COMPILE_CACHE=0 keeps its meaning (off, nothing touched)."""
    from kubeml_tpu.utils import env as env_mod

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fixed = os.path.join(repo, ".jax_cache")
    assert env_mod.DEFAULT_COMPILE_CACHE_DIR == fixed
    monkeypatch.setenv("KUBEML_TPU_HOME", str(tmp_path / "home"))
    monkeypatch.delenv("KUBEML_COMPILE_CACHE", raising=False)
    was = jax.config.jax_compilation_cache_dir
    placed = []
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(compilation_cache, "set_cache_dir", placed.append)
    try:
        if case == "env_set":
            outside = str(tmp_path / "operator_cache")
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
            assert env_mod.enable_compile_cache() == outside
            assert placed == []  # the directory is JAX's, not ours
            assert jax.config.jax_compilation_cache_dir == was
        elif case == "env_unset":
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert env_mod.enable_compile_cache() == fixed
            assert placed == [fixed]
            assert str(tmp_path) not in fixed
        else:
            monkeypatch.setenv("KUBEML_COMPILE_CACHE", "0")
            assert env_mod.enable_compile_cache() is None
            assert placed == []
            assert jax.config.jax_compilation_cache_dir == was
    finally:
        # the two admission thresholds are process-global; restore so
        # later tests keep the suite's default (no persistent cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          1.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
