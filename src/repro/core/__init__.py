from repro.core.fisher import (
    per_sample_fisher_scores,
    batch_fisher_scores,
    fim_diag,
    fim_momentum_update,
)
from repro.core.curriculum import (
    CurriculumSchedule,
    num_selected_batches,
    order_batches,
    selected_batch_ids,
)
from repro.core.gal import (
    adversarial_perturbation,
    layer_sensitivity_scores,
    aggregate_layer_scores,
    lossless_rank_fraction,
    select_gal_layers,
)
from repro.core.sparse import (
    neuron_importance,
    select_neuron_masks,
)
from repro.core.fibecfed import ENGINES, FibecFed, clear_compile_caches
from repro.core.engine import (
    build_round_fn,
    build_difficulty_fn,
    build_fim_warmup_fn,
    build_sharded_difficulty_fn,
    build_sharded_fim_warmup_fn,
    client_sharding,
    replicated_sharding,
)
