"""A cell of the chip benchmark cut to a size the CPU runs in seconds:
the harness's configuration and mix files with their widths, depth,
vocabulary and lengths shrunk, for rehearsals of everything a run does
after its look for a chip."""
import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import run  # noqa: E402

BENCH = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def tiny_cell(name: str):
    """(bench, cell, config, mix) of the cell ``name`` at a tiny size: the
    architecture's own shrink (``archs/<model_type>.py`` ``tiny``), the
    serving layout and the mix's lengths cut to fit."""
    cell = run.find(BENCH["workloads"], name, "workload")
    config = run.load_json(os.path.join(run.HERE, "configs",
                                        cell["config"] + ".json"))
    mix = run.load_json(os.path.join(run.HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return BENCH, cell, tiny_config(config), tiny_mix(mix)


def tiny_config(config: dict) -> dict:
    config = run.load_arch(config).tiny(copy.deepcopy(config))
    config["serving"].update(max_len=256, decode_batch=4, prefill_chunk=64,
                             capacity_prefill_chunk=64, queue_limit=8)
    # the limit at this size, set as the cells' are, from its own readings
    # (seeds 1-3, both cells): sound runs read widest gaps of 0 to 0.0063,
    # the float8 control 0.041 to 0.090
    config["check_limits"] = dict(config["check_limits"], max_logit_gap=0.02,
                                  min_checked_tokens=20)
    return config


def tiny_mix(mix: dict) -> dict:
    mix = copy.deepcopy(mix)
    mix["prompt"].update(median=48, min=16, max=160)
    mix["output"].update(median=12, min=4, max=64)
    if mix["arrivals"] == "poisson":
        mix["rate_rps"] = 3.0
    elif mix["arrivals"] == "sessions":
        # histories of a few pages, so that later turns hit the cache
        mix["prompt"].update(median=24, min=4, max=64)
        mix["output"].update(median=12, min=4, max=32)
        mix.update(rate_rps=3.0, max_prompt=160)
    else:
        mix["count"] = 30
    return mix
