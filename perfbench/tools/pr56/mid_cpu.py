"""PR 56, second session, no chip: does the served tokens' number mean
something once the reference follows the program over an answer's whole
length? A mid-size Keye stack in bf16 on the CPU (d 256, 6 layers, 8:2 heads
of 32, 4 indexer heads of 32 choosing 128 rows of 512-1,900, top-2 of 16
experts, vocabulary 32,768), through the real command's rehearsal, the
reference following 8 decoded positions and then 96 (every one), sound and
under the `int8` control:

    python3 perfbench/tools/pr56/mid_cpu.py /tmp/mid 8 && JAX_PLATFORMS=cpu python3 perfbench/run.py \
        --root /tmp/mid --workload toy-keye-serve --seed 2147483700 --seconds 8 --trace 0 --cpu-rehearsal [--control int8]

Read (three answers of 48-83 tokens): 8 followed: `mean_gap_spacings`
16.8-21.3 sound, 29.4-31.3 int8; every position followed: 0.008-0.020 sound,
0.12-0.56 int8. CPU readings of a toy: they say which way the number moves,
not what the cell reads."""

import importlib.util
import os
import sys
from pathlib import Path

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
spec = importlib.util.spec_from_file_location(
    "tk", os.path.join(ROOT, "tests", "perfbench", "test_perfbench_keye.py"))
tk = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tk)

tk.TOY.update({
    "head_dim": 32, "hidden_size": 256, "moe_intermediate_size": 128,
    "num_attention_heads": 8, "num_experts": 16, "num_local_experts": 16,
    "num_experts_per_tok": 2, "num_hidden_layers": 6, "num_key_value_heads": 2,
    "vocab_size": 32768, "rope_theta": 10000000, "torch_dtype": "bfloat16",
    "sa_config": {"indexer_head_dim": 32, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 128,
                  "q_chunk_size": 128, "topk": 128},
    "run": {"num_slots": 4, "max_len": 2048, "prefill_tokens": 2048,
            "max_concurrent_queries": 32}})
tk.TRAFFIC.update({
    "rate_per_s": 1.0, "request_timeout_s": 120,
    "prompt_tokens": {"log_mean": 6.8, "log_sd": 0.3, "min": 512, "max": 1900},
    "answer_tokens": {"log_mean": 3.8, "log_sd": 0.4, "min": 24, "max": 96},
    "warm": {"prefill_buckets": [512, 1024, 2048], "admission_batches": [1],
             "attention_buckets": [1024, 2048]},
    "check_decode_steps": int(sys.argv[2]),
    "limits": dict.fromkeys(tk.TRAFFIC["limits"], 1e9)})
root = Path(sys.argv[1])
root.mkdir(parents=True, exist_ok=True)
print(tk._throw_away_root(root))
