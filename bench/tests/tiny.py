"""A configuration and traffic small enough for the CPU, in the shapes of
the real cells: the harness runs them end to end in the tests."""
import json
from pathlib import Path

CONFIG = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, intermediate_size=128,
              vocab_size=256, torch_dtype="bfloat16",
              engine=dict(n_slots=4, max_len=256, block_size=16,
                          num_blocks=64, chunk_size=16, eos_id=-1))
TRAFFIC = {
    "chat": dict(rate_per_s=4.0, preroll_s=4.0,
                 prompt=dict(dist="lognormal", median=24, sigma=1.0, min=8,
                             max=64),
                 output=dict(dist="lognormal", median=8, sigma=0.8, min=4,
                             max=32)),
    "sessions": dict(kind="sessions", session_rate_per_s=1.0, preroll_s=4.0,
                     system_prompts=dict(count=3, length=40, zipf_s=1.1),
                     turns=dict(min=2, max=6), user=dict(min=4, max=12),
                     answer=dict(min=4, max=12), think_s_mean=0.2),
}
ROOT = Path(__file__).resolve().parents[2]


# readings at this size (CPU, bfloat16): the program's served gap is
# 0.0-0.009 on the seeds tried, the fp8 control's 0.09-0.12; a token
# altered where it is produced reads ~1 or more.  So the tiny limit sits
# at 0.03.
LIMITS = {"served_gap": 0.03}


def run(workload: str, traffic: str, *, seconds=3.0, seed=2 ** 40 + 3,
        config=None, **kw):
    from bench import harness

    return harness.run_cell(workload, seed, seconds, False,
                            require_tpu=False,
                            config_override={**CONFIG, **(config or {})},
                            traffic_override=TRAFFIC[traffic],
                            limits_override=LIMITS, **kw)


def config() -> dict:
    """The qwen3-0.6b file with the tiny sizes in place."""
    real = json.loads((ROOT / "bench/configs/qwen3-0.6b.json").read_text())
    return {**real, **CONFIG}
