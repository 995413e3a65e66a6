"""A tiny copy of the benchmark for CPU rehearsals: the same files, with each
configuration cut by its own architecture's ``tiny`` and the traffic cut to
what a test run can hold."""
import json
import os
import shutil

from benchmark.lib import spec as spec_mod

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _edit(path, fn):
    with open(path) as f:
        doc = json.load(f)
    fn(doc)
    with open(path, "w") as f:
        json.dump(doc, f)


def block(entries, names):
    """The entries named ``names``, which stand together in ``entries`` in
    that order, wherever the list puts them. Later PRs append to every list
    of BENCHMARK.json, so a test holds a block and its members, never a
    list's last entries."""
    listed = [e["name"] for e in entries]
    missing = [n for n in names if listed.count(n) != 1]
    assert not missing, f"{missing} are not each listed once"
    at = listed.index(names[0])
    assert listed[at:at + len(names)] == list(names), \
        f"{list(names)} do not stand together in this order"
    return entries[at:at + len(names)]


def copy_root(dst, src=ROOT):
    """Copy ``src``'s BENCHMARK.json and benchmark/ to ``dst`` as they are."""
    dst = str(dst)
    shutil.copytree(os.path.join(src, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(src, "BENCHMARK.json"), dst)
    return dst


def make_root(dst, src=ROOT):
    """``copy_root``, then cut down."""
    dst = copy_root(dst, src)
    spec = spec_mod.Spec(dst)
    for entry in spec.doc["configs"]:
        def cut(doc):
            doc.update(spec.module("arch", doc["arch"]).tiny(doc))
        _edit(os.path.join(dst, entry["file"]), cut)
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
