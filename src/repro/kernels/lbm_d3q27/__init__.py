from .ops import (  # noqa: F401
    TwoPhaseParams,
    config_space,
    equilibrium,
    hydro_step_ref,
    select_block,
    twophase_step,
    twophase_step_ref,
)
