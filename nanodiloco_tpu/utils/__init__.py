from nanodiloco_tpu.utils.utils import (
    allreduce_wire_report,
    create_run_name,
    device_memory_stats,
    enable_compile_cache,
    probe_backend,
    force_virtual_cpu_devices,
    require_accelerator,
    set_seed_all,
)

__all__ = [
    "allreduce_wire_report",
    "create_run_name",
    "device_memory_stats",
    "enable_compile_cache",
    "probe_backend",
    "force_virtual_cpu_devices",
    "require_accelerator",
    "set_seed_all",
]
