"""`keye_sa.index` under the name the `work_roofline` reader calls."""

from benchmark.work.keye_sa import index as work  # noqa: F401
