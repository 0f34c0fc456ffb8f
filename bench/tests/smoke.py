"""A cell at a size a CPU test run can hold: the deepseek-7b layout at
tiny widths, two paged replicas, a short chat-like mix."""

from __future__ import annotations

import copy

from bench.lib import spec

CONFIG = {
    "source": "test", "model_type": "llama", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 172, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 256,
    "rms_norm_eps": 1e-06, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "reference": "llama", "program_arch": "deepseek-7b",
    "serving": {"replicas": 2, "mesh": None, "max_batch": 4, "max_len": 64,
                "page_size": 16},
}
TRAFFIC = {
    "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 4, "max": 32},
    "prompt_grid": [8, 16, 24, 32],
    "output_tokens": {"dist": "uniform", "min": 2, "max": 12},
    "sizes_seed": 3,
}
PARAMS = {"rate_per_s": 40.0, "lead_in_s": 0.3, "profile_s": 0.5,
          "sample_tokens": 40, "limits": {"logit_gap": 1e-3}}


def cell(**changes) -> spec.Cell:
    """The smoke cell; ``changes`` override top-level config keys, or
    ``params``/``traffic``/``serving`` dicts."""
    config = copy.deepcopy(CONFIG)
    params = copy.deepcopy(PARAMS)
    traffic = copy.deepcopy(TRAFFIC)
    params.update(changes.pop("params", {}))
    traffic.update(changes.pop("traffic", {}))
    config["serving"].update(changes.pop("serving", {}))
    config.update(changes)
    bench = spec._load_json(spec.ROOT / "BENCHMARK.json")
    return spec.Cell("smoke", 1, config, traffic, params,
                     bench["end_to_end"], bench["per_layer"])


def run_line(cell: spec.Cell, seed: int = 3, seconds: float = 0.8,
             trace: bool = False) -> dict:
    """One run of ``cell`` on the CPU, with the look for a chip skipped;
    returns its result line."""
    import io
    import json

    from bench.lib import cell as cellmod

    out, err = io.StringIO(), io.StringIO()
    rc = cellmod.run(cell, seed=seed, seconds=seconds, trace=trace,
                     process_start=0.0, require_tpu=False, out=out, err=err)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1])
