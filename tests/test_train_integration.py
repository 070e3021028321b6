"""End-to-end integration: the paper's phenomena on synthetic MNIST, and
P2P training of the LLM substrate.  Slower tests (~2 min total on CPU)."""
import numpy as np
import pytest

from repro.configs.p2pl_mnist import directed_k8, noniid_k2
from repro.data import synthetic
from repro.launch.train import run_p2p_lm, run_paper_experiment


@pytest.fixture(scope="module")
def data():
    return synthetic.mnist_like(6000, 1500)


@pytest.fixture(scope="module")
def local_dsgd_log(data):
    return run_paper_experiment(
        noniid_k2(algorithm="local_dsgd", local_steps=10), rounds=12, data=data)


def test_forgetting_and_consensus_recovery(local_dsgd_log):
    """Fig. 3c: local training forgets unseen classes (down to ~0%), consensus
    restores them; accuracy after consensus > after local on unseen."""
    log = local_dsgd_log
    # device A (peer 0): unseen classes are peer 1's {7, 8}
    a_local = np.stack(log.after_local["peer1_seen"])[:, 0]
    a_cons = np.stack(log.after_consensus["peer1_seen"])[:, 0]
    assert a_local.min() < 0.05  # forgetting: drops to ~0% after local phase
    assert (a_cons - a_local).mean() > 0.1  # consensus recovers unseen classes


def test_seen_class_oscillation_is_opposite(local_dsgd_log):
    """Seen classes: local training helps, consensus pulls down (Fig. 3d)."""
    log = local_dsgd_log
    s_local = np.stack(log.after_local["peer0_seen"])[:, 0]
    s_cons = np.stack(log.after_consensus["peer0_seen"])[:, 0]
    assert (s_local - s_cons).mean() > 0.0


def test_affinity_damps_oscillations(data, local_dsgd_log):
    """Fig. 6: P2PL with Affinity reduces unseen-class oscillation amplitude
    vs. local DSGD at identical communication cost."""
    log_aff = run_paper_experiment(
        noniid_k2(algorithm="p2pl_affinity", local_steps=10), rounds=12,
        data=data)
    osc_plain = local_dsgd_log.mean_oscillation("peer1_seen")
    osc_aff = log_aff.mean_oscillation("peer1_seen")
    assert osc_aff < osc_plain, (osc_aff, osc_plain)


def test_dsgd_smaller_oscillation_than_local_dsgd(data, local_dsgd_log):
    """Fig. 4: fewer local steps between consensus -> smaller oscillations."""
    log_dsgd = run_paper_experiment(
        noniid_k2(algorithm="dsgd", local_steps=1), rounds=12, data=data)
    assert log_dsgd.mean_oscillation("peer1_seen") < local_dsgd_log.mean_oscillation(
        "peer1_seen"
    )


def test_drift_grows_locally_shrinks_at_consensus(local_dsgd_log):
    drift = np.asarray(local_dsgd_log.drift)  # recorded after local phase
    cons_err = np.asarray(local_dsgd_log.consensus_error)  # after consensus
    assert drift.mean() > cons_err.mean()


def test_directed_k8_push_sum_trains(data):
    """The directed-ring push-sum experiment runs end to end: finite losses,
    conserved mass, consensus actually mixes the one-way ring."""
    exp = directed_k8(schedule="static", protocol="push_sum",
                      algorithm="p2pl_affinity", local_steps=10)
    log = run_paper_experiment(exp, rounds=6, data=data)
    assert np.isfinite(log.train_loss).all()
    # consensus over the directed ring must pull peers together vs local drift
    assert np.asarray(log.consensus_error).mean() < np.asarray(log.drift).mean()


def test_cli_round_robin_and_protocol_flags(data, capsys, monkeypatch):
    """--schedule round_robin + --round-robin-topologies + --protocol are
    reachable from the command line (satellite: round_robin was Python-only)."""
    from repro.launch import train as train_mod

    monkeypatch.setattr(
        train_mod, "run_paper_experiment",
        # `data` binds the module fixture (main() never passes it): the CLI
        # test must run on the small dataset, not the 60k default
        lambda exp, rounds=None, **kw:
        run_paper_experiment(exp, rounds=1, data=data, **kw),
    )
    train_mod.main([
        "--experiment", "timevarying_k2", "--schedule", "round_robin",
        "--round-robin-topologies", "complete,disconnected",
        "--protocol", "push_sum", "--rounds", "1",
    ])
    assert "telemetry: spans" in capsys.readouterr().err


def test_cli_adaptive_composes_with_scan_driver(data, capsys, monkeypatch):
    """--schedule adaptive + --partner-rule + --adaptive-eps reach the
    runtime and compose with --driver scan (the default production driver)."""
    from repro.launch import train as train_mod

    seen = {}

    def _capture(exp, rounds=None, **kw):
        seen["exp"], seen["kw"] = exp, kw
        return run_paper_experiment(exp, rounds=1, data=data, **kw)

    monkeypatch.setattr(train_mod, "run_paper_experiment", _capture)
    train_mod.main([
        "--experiment", "timevarying_k2", "--schedule", "adaptive",
        "--partner-rule", "eps_greedy", "--adaptive-eps", "0.3",
        "--adaptive-seed", "7", "--driver", "scan", "--rounds", "1",
    ])
    assert "telemetry: spans" in capsys.readouterr().err
    assert seen["exp"].p2p.schedule == "adaptive"
    assert seen["exp"].p2p.partner_rule == "eps_greedy"
    assert seen["exp"].p2p.adaptive_eps == 0.3
    assert seen["exp"].p2p.adaptive_seed == 7
    assert seen["kw"]["driver"] == "scan"


def test_cli_rejects_unknown_partner_rule(capsys):
    from repro.launch import train

    with pytest.raises(SystemExit) as excinfo:
        train.main(["--experiment", "timevarying_k8", "--schedule", "adaptive",
                    "--partner-rule", "loss_proximty", "--rounds", "1"])
    assert excinfo.value.code == 2  # argparse choices error, before any jax work
    assert "--partner-rule" in capsys.readouterr().err


def test_cli_rejects_out_of_range_adaptive_eps(capsys):
    from repro.launch import train

    with pytest.raises(SystemExit) as excinfo:
        train.main(["--experiment", "timevarying_k8", "--schedule", "adaptive",
                    "--partner-rule", "eps_greedy", "--adaptive-eps", "1.5",
                    "--rounds", "1"])
    assert excinfo.value.code == 2
    assert "--adaptive-eps" in capsys.readouterr().err


@pytest.mark.skipif(
    __import__("jax").device_count() >= 2,
    reason="exercises the too-few-devices CLI error (single-device env only)",
)
def test_cli_adaptive_pod_still_fails_fast_on_missing_devices(capsys):
    """--schedule adaptive composes with --peer-axis pod: the device-count
    fail-fast (with the XLA_FLAGS hint) fires before tracing, exactly as on
    pretraced schedules."""
    from repro.launch import train

    with pytest.raises(SystemExit) as excinfo:
        train.main(["--experiment", "sharded_k8", "--schedule", "adaptive",
                    "--peer-axis", "pod", "--rounds", "1"])
    assert excinfo.value.code == 2
    err = capsys.readouterr().err
    assert "xla_force_host_platform_device_count" in err
    assert "num_peers=8" in err


def test_p2p_lm_training_reduces_loss_and_drift():
    """The paper's algorithm drives a (reduced) assigned arch: loss falls,
    consensus keeps peer models close."""
    out = run_p2p_lm("smollm-135m", num_peers=2, local_steps=4, rounds=25,
                     batch=8, seq=16, lr=5e-2, momentum=0.5)
    # vocab restricted to per-peer spans: achievable loss is ln(vocab/2),
    # ~0.7 nats under the ln(vocab) starting point — expect a clear drop
    assert min(out["losses"][-5:]) < out["losses"][0] - 0.3, out["losses"]
    assert np.isfinite(out["final_drift"])
