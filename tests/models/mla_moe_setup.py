"""A small configuration of the DeepSeek-V3 family for the CPU tests,
its weights drawn as the benchmark draws them, and the plain reference
(``bench/configs/mla_moe_decoder_ref.py``) beside the program."""

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from loader import family, load_module  # noqa: E402

SMALL = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=4, head_dim=16,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
             num_experts_per_tok=3, router_experts=16, n_routed_experts=8,
             expert_offset=4, vocab_size=512, param_dtype="float32",
             compute_dtype="float32")


def small_config(**kw) -> dict:
    """The cell's configuration file with small sizes: 1 dense and 2 MoE
    layers; 8 of 16 experts held (4-11), top 3."""
    c = json.loads((BENCH / "configs" / "moonlight-16b-a3b-ep8pp2.json")
                   .read_text())
    c.update(SMALL)
    c.update(kw)
    return c


def program(c):
    return family(c).program_config(c)


def reference(c):
    return load_module(BENCH / "configs" / c["reference"])


def weights(c, seed=5):
    import weights as weights_lib
    from repro.models import model_zoo
    return weights_lib.build(model_zoo.abstract_params(program(c)), c, seed)
