"""`afmoe_attn.full` under the name the `work_roofline` reader calls."""

from benchmark.work.afmoe_attn import full as work  # noqa: F401
