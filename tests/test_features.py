"""The declarative feature-compatibility table (core/features.py).

Three contracts:

* **Table integrity** — every incompatibility references registered features,
  carries a reason and a workaround, and the one formatter produces the
  documented ``A is not supported with B: reason; workaround`` shape.
* **Single source of truth** — the composition rejections that used to be
  scattered across ``P2PConfig.__post_init__``, ``make_sharded_round_fn``,
  the launcher, and argparse all fire FROM the table now: grepping the source
  tree finds the formatter's phrase in exactly one module.
* **Behavior** — configs that activate an incompatible pair are rejected with
  the table's message at every entry point (config construction for
  config-level pairs, the runtime/launcher for hierarchical pairs).
"""
import pathlib

import pytest

from repro.core import features, p2p

SRC = pathlib.Path(__file__).resolve().parent.parent / "src"


# ---------------------------------------------------------------------------
# table integrity
# ---------------------------------------------------------------------------


def test_every_incompatibility_references_registered_features():
    for inc in features.INCOMPATIBILITIES:
        assert inc.a in features.FEATURES, inc.a
        assert inc.b in features.FEATURES, inc.b
        assert inc.reason and inc.workaround


def test_feature_names_match_registry_keys():
    for name, feat in features.FEATURES.items():
        assert feat.name == name


def test_incompatibilities_are_unique_pairs():
    pairs = [frozenset((i.a, i.b)) for i in features.INCOMPATIBILITIES]
    assert len(pairs) == len(set(pairs))
    assert all(len(p) == 2 for p in pairs)  # no self-pairs


def test_formatter_shape():
    ctx = features.FeatureContext(schedule="adaptive", staleness_bound=2)
    (inc,) = features.violations(ctx)
    msg = features.format_violation(inc, ctx)
    a = features.FEATURES[inc.a].describe(ctx)
    b = features.FEATURES[inc.b].describe(ctx)
    assert msg == f"{a} is not supported with {b}: {inc.reason}; {inc.workaround}"


def test_active_features_reflect_context():
    ctx = features.FeatureContext()
    assert features.active_features(ctx) == ()
    ctx = features.FeatureContext(
        schedule="adaptive", compressor="topk", model="rwkv6_seqmnist",
        peers_per_device=2,
    )
    assert set(features.active_features(ctx)) == {
        "adaptive", "compression", "real_model", "hierarchical"
    }


def test_support_matrix_has_one_row_per_incompatibility():
    md = features.support_matrix_markdown()
    rows = [ln for ln in md.splitlines() if ln.startswith("|")]
    assert len(rows) == 2 + len(features.INCOMPATIBILITIES)  # header + rule


# ---------------------------------------------------------------------------
# single source of truth (the grep gate)
# ---------------------------------------------------------------------------


def test_formatter_phrase_appears_only_in_features_module():
    offenders = [
        p.relative_to(SRC)
        for p in SRC.rglob("*.py")
        if "is not supported with" in p.read_text() and p.name != "features.py"
    ]
    assert not offenders, (
        f"composition rejections outside core/features.py: {offenders}"
    )


# ---------------------------------------------------------------------------
# behavior at the entry points
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kwargs,first,second", [
    (dict(schedule="adaptive", staleness_bound=2), "staleness", "adaptive"),
    (dict(compressor="topk", staleness_bound=2), "staleness", "compressor"),
])
def test_config_level_pairs_reject_at_construction(kwargs, first, second):
    with pytest.raises(ValueError, match=second):
        p2p.P2PConfig(num_peers=8, **kwargs)


@pytest.mark.parametrize("kwargs,match", [
    (dict(schedule="adaptive"), "adaptive.*peers_per_device"),
    (dict(compressor="qint8"), "compressor.*peers_per_device"),
    (dict(steps_profile="straggler"), "steps-profile"),
    (dict(model="rwkv6_seqmnist"), "rwkv6_seqmnist.*hierarchical"),
])
def test_hierarchical_pairs_reject_with_peers_per_device(kwargs, match):
    cfg = p2p.P2PConfig(num_peers=8, **kwargs)
    with pytest.raises(ValueError, match=match):
        features.check_config(cfg, peers_per_device=2)
    # ... and compose fine with one peer per device
    features.check_config(cfg, peers_per_device=1)


def test_real_model_rejected_by_hier_round_step_builder():
    cfg = p2p.P2PConfig(num_peers=8, model="rwkv6_seqmnist")
    with pytest.raises(ValueError, match="rwkv6_seqmnist.*hierarchical"):
        p2p._make_round_step(
            lambda p, b: 0.0, cfg, mesh=object(), axis_name="pod",
            peers_per_device=2,
        )


def test_launcher_rejects_real_model_with_peers_per_device():
    from repro.configs.p2pl_mnist import seqmnist_k8
    from repro.launch import train

    with pytest.raises(ValueError, match="rwkv6_seqmnist.*hierarchical"):
        train.run_paper_experiment(
            seqmnist_k8(), rounds=1, peer_axis="pod", peers_per_device=2
        )


def test_cli_rejects_real_model_with_peers_per_device(capsys):
    from repro.launch import train

    with pytest.raises(SystemExit) as ex:
        train.main([
            "--experiment", "seqmnist_k8", "--peer-axis", "pod",
            "--peers-per-device", "2",
        ])
    assert ex.value.code != 0
    assert "rwkv6_seqmnist" in capsys.readouterr().err


def test_unknown_model_rejected_with_known_names():
    with pytest.raises(ValueError, match="unknown model.*mnist_mlp"):
        p2p.P2PConfig(model="resnet50")
