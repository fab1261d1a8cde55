"""`afmoe_attn.swa` under the name the `work_roofline` reader calls."""

from benchmark.work.afmoe_attn import swa as work  # noqa: F401
