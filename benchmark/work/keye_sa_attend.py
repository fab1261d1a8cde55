"""`keye_sa.attend` under the name the `work_roofline` reader calls."""

from benchmark.work.keye_sa import attend as work  # noqa: F401
