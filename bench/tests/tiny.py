"""A configuration and traffic small enough for the CPU, for the tests."""

import json

from bench import harness

CONFIG = {
    "name": "tiny-dense", "source": "test", "bench_model": "dense_decoder",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
}
TRAFFIC = {"argv": ["--batch", "4", "--seq-len", "64", "--num-docs", "256"],
           "storage": {"latency_s": 0.001}}
#: Set from CPU readings at this size (bf16 program against the float32
#: reference, 12 seeds): loss_gap at most 2.0e-4, grad_norm_gap 3.2e-3,
#: change_norm_gap 1.7e-3; the int8 control (3 seeds) read at least
#: 5.3e-4, 8.2e-3 and 4.0e-3, half the batch 0.012, 0.052 and 0.15.
LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 0.008, "change_norm_gap": 0.004,
          "rows_vs_host_loader": 0, "rows_vs_corpus": 0, "repeated_docs": 0}


def cell() -> "harness.Cell":
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    return harness.Cell("tiny-dense.test", 1, dict(CONFIG), dict(TRAFFIC), "tiny",
                        spec["end_to_end"], spec["per_layer"], dict(LIMITS))
