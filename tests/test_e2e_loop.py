"""End-to-end loop test (the milestone the reference never reached):

swarm sim → scheduler service → Download/topology records → announcer →
trainer → MLP+GNN trained → models in registry → activation → scheduler's
ML evaluator hot-swaps the scorer → learned ranking beats the rule-based
evaluator on ground-truth bandwidth.

Reference call stacks being exercised: SURVEY §3.1 (record birth),
§3.3 (probe loop), §3.4 (train loop — stubbed there, real here).
"""

import numpy as np
import pytest

from dragonfly2_tpu.manager import ClusterManager, ModelRegistry, ModelState
from dragonfly2_tpu.records.storage import Storage
from dragonfly2_tpu.scheduler import Announcer, Evaluator, MLEvaluator, ModelSubscriber
from dragonfly2_tpu.sim import SwarmConfig, SwarmSimulator
from dragonfly2_tpu.trainer.service import (
    GNN_MODEL_NAME,
    MLP_MODEL_NAME,
    TrainerService,
)
from dragonfly2_tpu.trainer.train import TrainConfig


@pytest.fixture(scope="module")
def loop_artifacts(tmp_path_factory):
    """Run the whole pipeline once; individual tests assert on the pieces."""
    root = tmp_path_factory.mktemp("e2e")
    storage = Storage(str(root / "scheduler-records"), buffer_size=50)
    sim = SwarmSimulator(storage, config=SwarmConfig(num_hosts=40, seed=7))

    # 1. Workload: downloads + probe rounds produce training data.
    sim.run_downloads(300, tasks=10)
    sim.run_probe_rounds(rounds=2)
    n_topo_records = sim.snapshot_topology()
    storage.flush()

    # 2. Train: announcer ships datasets to the trainer, which trains and
    #    registers models with the manager.
    registry = ModelRegistry()
    cluster_mgr = ClusterManager()
    trainer = TrainerService(
        registry,
        train_config=TrainConfig(epochs=25, learning_rate=3e-3, warmup_steps=20),
    )
    announcer = Announcer(
        "scheduler-1",
        storage,
        trainer,
        cluster_manager=cluster_mgr,
        ip="10.0.0.1",
        hostname="sched-1",
    )
    announcer.announce_to_manager()
    run_key = announcer.announce_to_trainer()
    run = trainer.runs[run_key]
    return {
        "sim": sim,
        "storage": storage,
        "registry": registry,
        "cluster_mgr": cluster_mgr,
        "trainer": trainer,
        "run": run,
        "n_topo_records": n_topo_records,
    }


class TestRecordProduction:
    def test_downloads_recorded(self, loop_artifacts):
        st = loop_artifacts["storage"]
        assert st.download_count >= 300
        downloads = st.list_download()
        with_parents = [d for d in downloads if d.parents]
        assert with_parents, "no download records carry parents"
        d = with_parents[0]
        assert d.parents[0].pieces, "parent entry lost its piece costs"
        assert d.parents[0].observed_bandwidth() > 0

    def test_topology_recorded(self, loop_artifacts):
        assert loop_artifacts["n_topo_records"] > 0
        assert loop_artifacts["storage"].network_topology_count > 0


class TestTrainRun:
    def test_run_succeeded(self, loop_artifacts):
        run = loop_artifacts["run"]
        assert run.error is None
        assert run.download_rows > 200
        assert run.topology_rows > 0
        assert len(run.models) == 2

    def test_mlp_metrics_meaningful(self, loop_artifacts):
        m = loop_artifacts["run"].metrics[MLP_MODEL_NAME]
        # log-space MAE must beat the predict-the-mean strawman by a margin.
        assert m.mae < 0.8, m
        assert m.f1 > 0.5, m

    def test_gnn_registered_with_metrics(self, loop_artifacts):
        reg = loop_artifacts["registry"]
        models = reg.list(scheduler_id="scheduler-1", name=GNN_MODEL_NAME)
        assert len(models) == 1
        assert "mae" in models[0].evaluation


class TestRegistryActivation:
    def test_single_active_per_name(self, loop_artifacts):
        reg = loop_artifacts["registry"]
        mlp = reg.list(scheduler_id="scheduler-1", name=MLP_MODEL_NAME)[0]
        reg.activate(mlp.id)
        # A second version created + activated deactivates the first.
        art = reg.load_artifact(mlp)
        m2 = reg.create_model(
            name=MLP_MODEL_NAME,
            type="mlp",
            scheduler_id="scheduler-1",
            artifact=art,
            evaluation={"mae": 0.0},
        )
        reg.activate(m2.id)
        states = {
            m.version: m.state
            for m in reg.list(scheduler_id="scheduler-1", name=MLP_MODEL_NAME)
        }
        assert states[m2.version] is ModelState.ACTIVE
        assert states[mlp.version] is ModelState.INACTIVE
        # Reactivate v1 for downstream tests.
        reg.activate(mlp.id)

    def test_keepalive_tracking(self, loop_artifacts):
        # The fixture registered the scheduler before it trained, which
        # may have taken longer than the TTL: hold the tracking to its
        # own clock arithmetic, not to how long the fixture took.
        cm = loop_artifacts["cluster_mgr"]
        assert cm.keepalive("scheduler-1") is True
        (inst,) = cm.active_schedulers()
        assert inst.id == "scheduler-1"
        inst.last_keepalive -= cm.ttl + 1.0
        assert cm.active_schedulers() == []
        assert cm.keepalive("scheduler-1") is True
        assert [s.id for s in cm.active_schedulers()] == ["scheduler-1"]
        assert cm.keepalive("no-such-scheduler") is False


class TestMLEvaluatorLoop:
    def test_subscriber_hot_swaps_scorer(self, loop_artifacts):
        reg = loop_artifacts["registry"]
        mlp = reg.list(scheduler_id="scheduler-1", name=MLP_MODEL_NAME)[0]
        reg.activate(mlp.id)
        ev = MLEvaluator()
        sub = ModelSubscriber(reg, ev, scheduler_id="scheduler-1")
        assert sub.refresh() is True
        assert ev.has_model
        # Deactivate → falls back to rules.
        reg.deactivate(mlp.id)
        assert sub.refresh() is True
        assert not ev.has_model
        reg.activate(mlp.id)

    def test_learned_ranking_beats_rules(self, loop_artifacts):
        reg = loop_artifacts["registry"]
        sim = loop_artifacts["sim"]
        mlp = reg.list(scheduler_id="scheduler-1", name=MLP_MODEL_NAME)[0]
        reg.activate(mlp.id)
        ml_ev = MLEvaluator()
        ModelSubscriber(reg, ml_ev, scheduler_id="scheduler-1").refresh()
        assert ml_ev.has_model

        rules_bw = sim.measure_parent_choice_quality(Evaluator(), n_trials=60)
        ml_bw = sim.measure_parent_choice_quality(ml_ev, n_trials=60)
        # BASELINE configs[2]: the learned evaluator must beat the
        # rule-based one on achieved bandwidth of the chosen parent.
        assert ml_bw > rules_bw, (ml_bw, rules_bw)


class TestGNNServing:
    def test_gnn_scorer_artifact_serves(self, loop_artifacts):
        """The GNN model's artifact is a real scorer: embedding-table lookup
        + head, loadable by the subscriber and usable for ranking."""
        reg = loop_artifacts["registry"]
        sim = loop_artifacts["sim"]
        gnn = reg.list(scheduler_id="scheduler-1", name=GNN_MODEL_NAME)[0]
        assert len(reg.load_artifact(gnn)) > 0
        reg.activate(gnn.id)
        ev = MLEvaluator()
        sub = ModelSubscriber(
            reg, ev, scheduler_id="scheduler-1", model_name=GNN_MODEL_NAME
        )
        assert sub.refresh() is True
        assert ev.has_model
        quality = sim.measure_parent_choice_quality(ev, n_trials=40)
        assert np.isfinite(quality) and quality > 0
