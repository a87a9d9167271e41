"""A benchmark at a tiny size in a temporary directory, made of NEW files
and entries alone: two configurations, three training jobs (one of them
data-parallel over four virtual devices), three cells and one per-layer
metric.  Nothing of ``benchmark/`` is edited or copied — the
harness finds families, runners and readers in its own directory and
everything else under the temporary root, by name.
"""
import json
import os
import time

REAL = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GPT2_TINY = {
    "name": "gpt2-tiny", "family": "gpt2", "activation_function": "gelu_new",
    "attn_pdrop": 0.0, "embd_pdrop": 0.0, "resid_pdrop": 0.0,
    "initializer_range": 0.02, "layer_norm_epsilon": 1e-5, "n_ctx": 128,
    "n_embd": 128, "n_head": 2, "n_layer": 2, "n_positions": 128,
    "vocab_size": 1000, "assumed": {"padded_vocab_size": 1024},
    "precision": {"opt_level": "O2"},
}
BERT_TINY = {
    "name": "bert-tiny", "family": "bert",
    "attention_probs_dropout_prob": 0.0, "hidden_dropout_prob": 0.0,
    "hidden_act": "gelu", "hidden_size": 128, "initializer_range": 0.02,
    "intermediate_size": 512, "layer_norm_eps": 1e-12,
    "max_position_embeddings": 128, "num_attention_heads": 2,
    "num_hidden_layers": 2, "type_vocab_size": 2, "vocab_size": 1000,
    "assumed": {"padded_vocab_size": 1024, "mlm_probability": 0.15},
    "precision": {"opt_level": "O2"},
}
TRAIN_LIMITS = {"loss_rel_gap": 1e-3, "grad_norm_rel_gap": 0.05,
                "param_delta_leaf_gap": 0.1}
ADAMW = {"name": "adamw", "lr": 6e-4, "wd": 0.1, "eps": 1e-8}
LAMB = {"name": "lamb", "lr": 1e-3, "wd": 0.01, "eps": 1e-6}


def train_mix(objective, optimizer, rows=4, **extra):
    return {"kind": "train", "objective": objective, "rows": rows, "seq": 128,
            "steps_per_dispatch": 3, "optimizer": optimizer,
            "reference_rows_per_block": 2, "limits": TRAIN_LIMITS, **extra}


NEW_METRIC = '''"""Layer: train loop.  Windows measured (a metric added by a file)."""


def read(run):
    return len(run["window_ms"]) if run.get("window_ms") else None
'''


def make_root(root: str) -> str:
    """Write the tiny benchmark under ``root`` and return ``root``."""
    for sub in ("configs", "traffic", "layer_metrics"):
        os.makedirs(os.path.join(root, "benchmark", sub), exist_ok=True)

    def put(sub, name, obj):
        with open(os.path.join(root, "benchmark", sub, name), "w") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))

    put("configs", "gpt2-tiny.json", GPT2_TINY)
    put("configs", "bert-tiny.json", BERT_TINY)
    put("traffic", "lm-tiny.json", train_mix("causal_lm", ADAMW))
    put("traffic", "mlm-tiny.json", train_mix("mlm", LAMB))
    put("traffic", "mlm-tiny-dp4.json",
        train_mix("mlm", LAMB, rows=8, data_parallel=True))
    put("layer_metrics", "train.windows.py", NEW_METRIC)

    with open(os.path.join(REAL, "BENCHMARK.json")) as f:
        real = json.load(f)
    cells = [("gpt2-tiny.train", "gpt2-tiny", "lm-tiny", 1),
             ("bert-tiny.train", "bert-tiny", "mlm-tiny", 1),
             ("bert-tiny.train-dp4", "bert-tiny", "mlm-tiny-dp4", 4)]
    train = [c[0] for c in cells]

    def retarget(metric):
        """A real entry, for the tiny cells."""
        return {**metric, **({"workloads": train} if "workloads" in metric
                             else {})}

    bench = {
        "command": real["command"], "paths": real["paths"], "run_seconds": 2,
        "configs": [{"name": n, "source": "test", "reduced": [], "why": "tiny",
                     "file": f"benchmark/configs/{n}.json"}
                    for n in ("gpt2-tiny", "bert-tiny")],
        "workloads": [{"name": n, "config": c, "traffic": t, "chips": k,
                       "why": "tiny"} for n, c, t, k in cells],
        "end_to_end": [retarget(m) for m in real["end_to_end"]],
        "per_layer": [retarget(m) for m in real["per_layer"]] + [{
            "name": "train.windows", "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "train loop",
            "moves": "train_tokens_per_s", "workloads": train}],
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def fake_device(chips: int):
    """Stands in for the harness's look for a chip (and nothing else)."""
    import jax

    d = jax.devices()
    assert len(d) >= chips
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def run_cell(root, workload, capsys, *, seed=3, seconds=1.0, trace=0,
             device_check=fake_device):
    """One run through ``harness.main`` with the device check injected;
    returns (exit code, the result line as a dict, every printed line)."""
    from benchmark import harness

    rc = harness.main(
        ["--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)],
        root, time.perf_counter(), device_check=device_check)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1]), lines
