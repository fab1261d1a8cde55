from triton_dist_tpu.runtime.bootstrap import (  # noqa: F401
    initialize_distributed,
    finalize_distributed,
    get_context,
    DistContext,
    interpret_mode,
    shmem_compiler_params,
    make_mesh,
    auto_mesh,
    place_compile_cache,
    on_tpu,
    next_collective_id,
)
from triton_dist_tpu.runtime.symm_mem import (  # noqa: F401
    SymmetricWorkspace,
    create_symm_buffer,
    clear_registry,
)
from triton_dist_tpu.runtime.telemetry import (  # noqa: F401
    Counter,
    DEFAULT_SLO_CLASSES,
    Gauge,
    Histogram,
    MetricsRegistry,
    Telemetry,
    default_registry,
    escape_label_value,
    labeled_name,
    prometheus_text,
    trace_comm_kernel,
)
