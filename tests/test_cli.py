"""CLI surface (roc_tpu/train/cli.py): flag plumbing, validation, and
the train/eval/checkpoint entry points, in-process on CPU."""

import numpy as np
import pytest

from roc_tpu.train import cli


def _run(argv):
    return cli.main(["--cpu", "--no-compile-cache"] + argv)


def test_synthetic_train_succeeds(capsys):
    rc = _run(["-e", "3", "-layers", "8-8-3", "--eval-every", "3",
               "--impl", "ell"])
    assert rc == 0
    assert "[INFER]" in capsys.readouterr().out


def test_checkpoint_resume_eval_only(tmp_path, capsys):
    ck = str(tmp_path / "ck.npz")
    assert _run(["-e", "3", "-layers", "8-8-3", "--impl", "ell",
                 "--checkpoint", ck]) == 0
    capsys.readouterr()
    rc = _run(["-e", "3", "-layers", "8-8-3", "--impl", "ell",
               "--resume", ck, "--eval-only"])
    assert rc == 0
    out = capsys.readouterr().out
    # one INFER line at the restored epoch, no training
    assert out.count("[INFER]") == 1
    assert "[INFER][3]" in out


@pytest.mark.parametrize("argv,msg", [
    (["-layers", "8"], "at least"),
    (["--model", "gcn", "--heads", "4", "-layers", "8-8-3"],
     "--heads applies"),
    (["--model", "gat", "--heads", "0", "-layers", "8-8-3"],
     ">= 1"),
    (["--model", "gat", "--heads", "3", "-layers", "8-8-3"],
     "divisible"),
    (["--halo", "ring", "-layers", "8-8-3"], "--parts"),
    (["--model", "gcn", "--learn-eps", "-layers", "8-8-3"],
     "--learn-eps applies"),
    (["--model", "gcn", "--skip", "-layers", "8-8-3"],
     "--skip/--act/--input-dropout apply"),
    (["--model", "sage", "--act", "elu", "-layers", "8-8-3"],
     "--skip/--act/--input-dropout apply"),
    (["--model", "gcn", "--input-dropout", "0.1", "-layers", "8-8-3"],
     "--skip/--act/--input-dropout apply"),
    (["--model", "gat", "--input-dropout", "1.0", "-layers", "8-8-3"],
     "--input-dropout must be"),
])
def test_flag_validation_fails_fast(argv, msg, capsys):
    assert _run(argv) == 2
    assert msg in capsys.readouterr().err


def test_save_logits_matches_metrics(tmp_path, capsys):
    """--save-logits writes [V, C] fp32 whose argmax reproduces the
    printed test accuracy — i.e. the export really is the final
    model's inference output."""
    import re
    path = str(tmp_path / "lg.npy")
    rc = _run(["-e", "3", "-layers", "8-8-3", "--impl", "ell",
               "--eval-every", "3", "--save-logits", path])
    assert rc == 0
    out = capsys.readouterr().out
    printed = re.findall(r"test_accuracy: [\d.]+%\((\d+)/(\d+)\)", out)
    assert printed, out
    correct, cnt = map(int, printed[-1])
    logits = np.load(path)
    assert logits.shape[1] == 3 and logits.dtype == np.float32
    # recompute test accuracy from the exported logits
    from roc_tpu.core.graph import MASK_TEST, synthetic_dataset
    ds = synthetic_dataset(512, 8, in_dim=8, num_classes=3, seed=1)
    sel = ds.mask == MASK_TEST
    got_correct = int((np.argmax(logits[sel], axis=1)
                       == ds.labels[sel]).sum())
    assert (got_correct, int(sel.sum())) == (correct, cnt)


def test_save_logits_reorder_inverts_to_original_order(tmp_path):
    """The same (seeded) run with and without --reorder bfs must save
    logits for the same vertices in the same ORIGINAL order — the
    permutation round-trips."""
    outs = {}
    for tag, extra in (("plain", []), ("bfs", ["--reorder", "bfs"])):
        path = str(tmp_path / f"{tag}.npy")
        rc = _run(["-e", "4", "-layers", "8-8-3", "--impl", "ell",
                   "-dropout", "0.0", "--eval-every", "1000",
                   "--save-logits", path] + extra)
        assert rc == 0
        outs[tag] = np.load(path)
    # identical params (graph-independent init) + relabeling-invariant
    # math => logits match vertex-for-vertex up to fp association
    np.testing.assert_allclose(outs["plain"], outs["bfs"],
                               rtol=2e-3, atol=2e-4)


def test_distributed_predict_matches_single():
    """DistributedTrainer.predict returns original-order logits equal
    to the single-device forward for the same params."""
    import jax
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.trainer import TrainConfig, Trainer
    ds = synthetic_dataset(128, 6, in_dim=8, num_classes=3, seed=4)
    model = build_gcn([8, 8, 3], dropout_rate=0.0)
    cfg = TrainConfig(aggr_impl="ell", verbose=False, chunk=64,
                      eval_every=1 << 30)
    dt = DistributedTrainer(model, ds, 4, cfg)
    tr = Trainer(model, ds, cfg)
    tr.params = jax.device_get(dt.params)
    np.testing.assert_allclose(np.asarray(dt.predict()),
                               np.asarray(tr.predict()),
                               rtol=1e-4, atol=1e-5)


def test_gin_learn_eps_cli(capsys):
    rc = _run(["-e", "2", "-layers", "8-8-3", "--model", "gin",
               "--learn-eps", "--impl", "ell", "--eval-every", "2"])
    assert rc == 0
    assert "[INFER]" in capsys.readouterr().out


def test_gat_mixed_distributed(capsys):
    rc = _run(["-e", "2", "-layers", "8-8-3", "--model", "gat",
               "--heads", "2", "--dtype", "mixed", "--parts", "2",
               "--eval-every", "2"])
    assert rc == 0
    assert "[INFER]" in capsys.readouterr().out


def test_gat_published_flags_reach_the_model(capsys, tmp_path):
    """The OGB full-batch GAT's flags through the CLI: the skip, ReLU
    and the input-dropout rate reach the built model, whose manifest
    then carries one ``attention`` entry per attention op."""
    import json
    events = str(tmp_path / "events.jsonl")
    seen = []
    rc = cli.main(["--cpu", "--no-compile-cache", "-e", "2", "-layers",
                   "8-12-12-3", "--model", "gat", "--heads", "3",
                   "--skip", "--act", "relu", "-dropout", "0.75",
                   "--input-dropout", "0.1", "--eval-every", "2",
                   "--events", events], inspect=seen.append)
    assert rc == 0
    assert "[INFER]" in capsys.readouterr().out
    ops = seen[0].model._ops
    assert sum(op.kind == "linear" for op in ops) == 6
    assert [op.attrs["rate"] for op in ops if op.kind == "dropout"] == [
        0.1, 0.75, 0.75]
    assert {op.attrs["mode"] for op in ops
            if op.kind == "activation"} == {"relu"}
    with open(events) as f:
        man = [json.loads(ln) for ln in f if '"manifest"' in ln][-1]
    assert [(e["op"], e["heads"], e["head_width"], e["layout"],
             e["edge_passes"], e["carry_rows"])
            for e in man["resolved"]["attention"]] == [
        (3, 3, 4, "ell", 1, None), (9, 3, 4, "ell", 1, None),
        (15, 1, 3, "ell", 1, None)]


def test_cli_sgc_model_trains():
    """--model sgc --hops: the SGC family end-to-end through the CLI."""
    rc = _run(["--model", "sgc", "--hops", "2", "-layers", "12-4",
               "-e", "3", "-lr", "0.2"])
    assert rc == 0


def test_cli_appnp_model_trains_and_validates():
    """--model appnp end-to-end, and --alpha misuse fails fast (before
    any dataset load): on a non-appnp model, and out of [0, 1]."""
    rc = _run(["--model", "appnp", "--hops", "3", "--alpha", "0.2",
               "-layers", "12-8-4", "-e", "3", "-lr", "0.05"])
    assert rc == 0
    assert _run(["--model", "gcn", "--alpha", "0.3",
                 "-layers", "12-4", "-e", "1"]) == 2
    # the default VALUE passed explicitly is still misuse (sentinel)
    assert _run(["--model", "gcn", "--alpha", "0.1",
                 "-layers", "12-4", "-e", "1"]) == 2
    # --hops rides the same sentinel policy
    assert _run(["--model", "gcn", "--hops", "2",
                 "-layers", "12-4", "-e", "1"]) == 2
    assert _run(["--model", "appnp", "--hops", "0",
                 "-layers", "12-4", "-e", "1"]) == 2
    assert _run(["--model", "appnp", "--alpha", "1.5",
                 "-layers", "12-4", "-e", "1"]) == 2


def test_cli_gcn2_model_trains_and_validates():
    """--model gcn2 end-to-end (deep stack), and --lam / hidden-width
    / depth misuse fails fast (exit 2, before any dataset load)."""
    rc = _run(["--model", "gcn2", "-layers", "12-16-16-16-4",
               "-e", "3", "-lr", "0.05"])
    assert rc == 0
    assert _run(["--model", "gcn", "--lam", "0.5",
                 "-layers", "12-4", "-e", "1"]) == 2
    assert _run(["--model", "gcn2", "--lam", "0",
                 "-layers", "12-16-4", "-e", "1"]) == 2
    # structural -layers misuse: mismatched widths / no hidden layer
    assert _run(["--model", "gcn2", "-layers", "12-16-24-4",
                 "-e", "1"]) == 2
    assert _run(["--model", "gcn2", "-layers", "12-4", "-e", "1"]) == 2


# ---- a typed graph (--model rgcn): kinds on the command line ----

@pytest.mark.parametrize("argv,msg", [
    (["--model", "rgcn", "-layers", "8-8-3"], "needs --node-types"),
    (["--model", "gcn", "--node-types", "300,212", "-layers", "8-8-3"],
     "apply to --model rgcn only"),
    (["--model", "sage", "--embed-types", "1", "-layers", "8-8-3"],
     "apply to --model rgcn only"),
    (["--model", "rgcn", "--node-types", "300,x", "-layers", "8-8-3"],
     "comma-separated integers"),
    (["--model", "rgcn", "--node-types", "300,212", "--embed-types", "0",
      "-layers", "8-8-3"], "kind 0 carries"),
    (["--model", "rgcn", "--node-types", "300,212", "--embed-types", "2",
      "-layers", "8-8-3"], "name no kind"),
    (["--model", "rgcn", "--node-types", "300,212", "--parts", "2",
      "-layers", "8-8-3"], "runs on one chip"),
    (["--model", "rgcn", "--node-types", "300,212", "--halo", "ring",
      "-layers", "8-8-3"], "--halo ring"),
    (["--model", "rgcn", "--node-types", "300,212", "--reorder", "bfs",
      "-layers", "8-8-3"], "contiguous id ranges"),
    (["--model", "rgcn", "--node-types", "300,212", "--impl", "ell",
      "-layers", "8-8-3"], "no 'ell' layout"),
    # the kinds must count the graph's vertices (the synthetic smoke
    # graph holds 512)
    (["--model", "rgcn", "--node-types", "300,200", "-layers", "8-8-3"],
     "the graph holds 512"),
])
def test_typed_flag_validation_fails_fast(argv, msg, capsys):
    assert _run(argv) == 2
    assert msg in capsys.readouterr().err


def test_cli_rgcn_on_the_synthetic_graph(capsys):
    """Two kinds over the homogeneous smoke graph: every ordered pair
    that occurs is a relation, kind 1 trains an embedding table, and
    ``auto`` takes the flat scan."""
    seen = {}
    rc = cli.main(["--cpu", "--no-compile-cache", "--model", "rgcn",
                   "-layers", "8-8-3", "--node-types", "300,212",
                   "--embed-types", "1", "-decay", "0", "-e", "5",
                   "--eval-every", "5"],
                  inspect=lambda tr: seen.update(tr=tr))
    assert rc == 0
    tr = seen["tr"]
    assert tr.model.typed["relations"] == ((0, 0), (0, 1), (1, 0), (1, 1))
    assert tr.config.aggr_impl == "flat_sum"
    assert tr.params["embed_1"].shape == (212, 8)
    assert tr.feats.shape == (300, 8)
    assert "[INFER]" in capsys.readouterr().out


@pytest.mark.parametrize("argv,msg", [
    (["--model", "gcn", "--t", "0.1", "-layers", "8-8-3"],
     "--t applies to --model deepergcn only"),
    (["--model", "deepergcn", "--t", "0", "-layers", "8-8-8-3"],
     "--t must be > 0"),
    (["--model", "deepergcn", "-layers", "8-3"],
     "at least one GENConv layer"),
    (["--model", "deepergcn", "-layers", "8-8-16-3"],
     "hidden widths must all match"),
])
def test_deepergcn_flag_validation_fails_fast(argv, msg, capsys):
    assert _run(argv) == 2
    assert msg in capsys.readouterr().err


@pytest.mark.parametrize("extra", [[], ["--parts", "2"],
                                   ["--dtype", "mixed"]])
def test_cli_deepergcn_trains_evaluates_and_resumes(extra, tmp_path,
                                                    capsys):
    """--model deepergcn through resolve_config and either trainer:
    trains, prints an inference pass, saves; the resumed run's eval
    reads the saved running statistics (same [INFER] line)."""
    ck = str(tmp_path / "ck.npz")
    base = ["--model", "deepergcn", "-layers", "12-16-16-16-4", "--t",
            "0.1", "-decay", "0", "--eval-every", "3"] + extra
    seen = {}
    rc = cli.main(["--cpu", "--no-compile-cache", "-e", "3",
                   "--checkpoint", ck] + base,
                  inspect=lambda tr: seen.update(tr=tr))
    assert rc == 0
    tr = seen["tr"]
    assert [op.kind for op in tr.model._ops].count("batch_norm") == 3
    assert [op.kind for op in tr.model._ops].count("soft_aggregate") == 3
    assert np.abs(np.asarray(tr.params["bn_0_mean"])).max() > 0
    assert set(tr.opt_state.m) == set(tr.params) - set(
        tr.model.state_names())
    first = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[INFER][2]")]
    assert first
    assert _run(["-e", "3", "--resume", ck, "--eval-only"] + base) == 0
    again = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[INFER]")]
    assert len(again) == 1


def test_help_lists_deepergcn_with_its_source(capsys):
    with pytest.raises(SystemExit):
        cli.parse_args(["--help"])
    out = capsys.readouterr().out
    assert "deepergcn" in out and "arXiv:2006.07739" in " ".join(
        out.split())
