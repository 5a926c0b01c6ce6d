"""The step of the cell whose routing is the seed's own (``mellum2-ft1``): the same
90th percentile of the stamp-to-stamp interval as ``step_p90_ms``, under a
bound of its own (PERF.md section 2)."""

from benchmark import common

read = common.load_by_name("end_to_end", "step_p90_ms").read
