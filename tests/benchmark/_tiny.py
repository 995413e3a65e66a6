"""A tiny copy of the benchmark for CPU rehearsals: the same files, with the
widths, the engine and the traffic cut to what a test run can hold."""
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_WIDTHS = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                   num_attention_heads=8, num_key_value_heads=2, head_dim=16,
                   max_position_embeddings=256, num_hidden_layers=2)


def _edit(path, fn):
    with open(path) as f:
        doc = json.load(f)
    fn(doc)
    with open(path, "w") as f:
        json.dump(doc, f)


def make_root(dst):
    """Copy BENCHMARK.json and benchmark/ to ``dst`` and cut them down."""
    dst = str(dst)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    cfgs = os.path.join(dst, "benchmark", "configs")
    for name in os.listdir(cfgs):
        def cut(doc):
            doc.update(TINY_WIDTHS)
            if "engine" in doc:
                doc["engine"] = {"block_size": 16, "max_slots": 4,
                                 "max_model_len": 128}
                # float32 here, so that the program sits far inside the
                # limit that the int8 control has to break
                doc["dtype"] = "float32"
        _edit(os.path.join(cfgs, name), cut)
    mixes = os.path.join(dst, "benchmark", "traffic")

    def closed(doc):
        doc.update(clients=4, round=8, warm_prompt_lens=[16, 32, 64],
                   trace_seconds=2,
                   prompt_len={"kind": "lognormal", "median": 24,
                               "sigma": 0.5, "min": 10, "max": 48},
                   output_len={"kind": "lognormal", "median": 12,
                               "sigma": 0.4, "min": 6, "max": 20})
        doc["check"].update(requests=64, pad_to=128, rows=8)
        doc["check"]["limits"] = {"logit_gap_max": 2e-3,
                                  "logit_gap_mean": 2e-5,
                                  "compared_tokens_min": 10}

    def opened(doc):
        closed(doc)
        doc.update(ramp_s=1, arrival={"kind": "poisson", "rate_qps": 6.0},
                   warm_prompt_lens=[32, 64, 100],
                   prompt_len={"kind": "lognormal", "median": 40,
                               "sigma": 0.8, "min": 17, "max": 100},
                   output_len={"kind": "lognormal", "median": 8,
                               "sigma": 0.6, "min": 4, "max": 16})

    def train(doc):
        doc.update(batch=2, seq=64, trace_seconds=1)
        doc["check"]["limits"] = {"loss_gap_max": 1e-5,
                                  "grad_norm_gap_max": 1e-4,
                                  "update_norm_gap_max": 1e-4}

    _edit(os.path.join(mixes, "decode-closed.json"), closed)
    _edit(os.path.join(mixes, "chat-open.json"), opened)
    _edit(os.path.join(mixes, "train-2k.json"), train)
    return dst
