from repro.models.model import (  # noqa: F401
    build_plan,
    cache_batch_axes,
    decode_loop,
    decode_loop_mtp,
    decode_step,
    decode_step_held,
    forward,
    init_params,
    lm_loss,
    make_caches,
    prefill,
    prefill_continue,
)
