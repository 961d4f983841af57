"""A tiny cell for the CPU tests: the same harness, engine and reference
as the chip cells, at sizes the Pallas interpreter runs in seconds."""
from bench import spec as SP

CONFIG = {
    "name": "tiny", "policy": "w4a16kv8",
    "model": {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 32, "d_ff": 256, "vocab": 512,
              "tie_embeddings": False, "rope_theta": 10000.0,
              "rotary_pct": 0.5, "norm_eps": 1e-5}}

TRAFFIC = {
    "arrivals": {"kind": "poisson", "rate_per_s": 6.0},
    "prompt": {"median": 10, "sigma": 0.5, "min": 4, "max": 22},
    "output": {"median": 5, "sigma": 0.5, "min": 2, "max": 9},
    "warmup_s": 0.5,
    "engine": {"cache_kind": "paged", "attn_impl": "kernel", "n_slots": 4,
               "max_seq": 32, "block_size": 16, "prefill_chunk": 16,
               "enable_prefix_caching": False, "seed": 0}}

#: the same, offered by a closed loop of four clients that send again at
#: once
CLOSED_TRAFFIC = dict(TRAFFIC, arrivals={"kind": "closed", "clients": 4,
                                         "think_s": 0.0})

#: set from CPU readings of this tiny cell at a 1.5 s window: the program
#: reads 0.0 to 0.0126 over twelve seeds, the kv4 control 0.054 to 0.170;
#: at this seed 0.0 and 0.147
LIMIT = 0.03
SEED = 2 ** 31 + 78


def cell(name="smollm360m.alpaca_poisson", traffic=TRAFFIC) -> SP.Cell:
    spec = SP.benchmark()
    return SP.Cell(name=name, chips=1, config=CONFIG, traffic=traffic,
                   limits={"max_logit_gap": LIMIT},
                   end_to_end=SP.metrics_for(spec, "end_to_end", name),
                   per_layer=SP.metrics_for(spec, "per_layer", name))
