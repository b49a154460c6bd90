"""Whole-chain fusion: compile operator chains into one XLA dispatch.

The executor half of the fusion story (ROADMAP item 1): the advisor
(windflow_tpu/analysis/fusion.py) *plans* maximal fusible chains; this
package *executes* them — at ``PipeGraph._build`` each executable chain
lowers into ONE ``wf_jit`` program per batch sweep, with the sweep
ledger (monitoring/sweep_ledger.py) attributing the before/after
dispatch and HBM-byte savings.  See ``fusion/executor.py`` for the
mechanism.
"""

from windflow_tpu.fusion.executor import (apply_fusion,
                                          attribute_member_stats,
                                          build_prelude, fused_name,
                                          plan_segments)

__all__ = ["apply_fusion", "attribute_member_stats", "build_prelude",
           "fused_name", "plan_segments"]
