"""The Mixtral family (``model_type`` ``mixtral``): the layers of
``qwen3_moe`` (grouped-query attention, then top-k routing over SwiGLU
experts in every layer). Its softmax over the top-2 router logits equals
the top-2 softmax scores renormalised, and ``sizes`` reads Mixtral's keys
(``num_local_experts``, ``intermediate_size``)."""
from bench.families.qwen3_moe import (Reference, calibrate_2t, layout,
                                      model_config, model_flops, moe_needed,
                                      sizes)

__all__ = ["Reference", "calibrate_2t", "layout", "model_config",
           "model_flops", "moe_needed", "sizes"]
