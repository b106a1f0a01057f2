from .transformer import ModelConfig, init_params, forward, forward_with_aux, param_specs
from .train import (TrainConfig, make_mesh, init_train_state, train_step,
                    loss_fn, packed_fields)
from .decode import Cache, forward_cached, generate, init_cache, prefill, sample_logits
from .dist_decode import DistCache, dist_generate, dist_prefill
from .paged_decode import (
    PagePool, PagedState, PrefixCache, ensure_capacity, init_paged_state,
    paged_decode_step, paged_multi_step, paged_prefill,
    provision_capacity, retire_slot, rollback_tokens,
)
from .pipeline_lm import stack_layers, unstack_layers
from .serve import ServeEngine
from .speculative import SpecStats, speculative_generate

__all__ = [
    "sample_logits",
    "stack_layers",
    "unstack_layers",
    "ModelConfig",
    "init_params",
    "forward",
    "forward_with_aux",
    "param_specs",
    "TrainConfig",
    "make_mesh",
    "init_train_state",
    "train_step",
    "packed_fields",
    "loss_fn",
    "Cache",
    "forward_cached",
    "generate",
    "init_cache",
    "prefill",
    "DistCache",
    "dist_generate",
    "dist_prefill",
    "PagePool",
    "PagedState",
    "PrefixCache",
    "ensure_capacity",
    "init_paged_state",
    "paged_decode_step",
    "paged_prefill",
    "paged_multi_step",
    "provision_capacity",
    "rollback_tokens",
    "retire_slot",
    "ServeEngine",
    "SpecStats",
    "speculative_generate",
]
