"""Steady federated rounds of a model whose expert layers route each token
to its top-k experts: the ``rounds`` job, with the comparison that decides
``correct`` suited to routing.

Set-up, window, end-to-end metric and required operations are the
``rounds`` job's. The comparison differs in one way. A routed layer's
selection is discrete: where two experts' scores lie within bfloat16's
rounding of each other, the program and the float32 reference send a token
to different experts, and the loss of the rounds after the first, which
falls as the clients learn their labels, moves far more than rounding
alone would move it. ``rounds``' ``loss_gap`` (the worst checked round's
mean loss) then reads as high in sound runs as in the float8 control. Here
the loss is compared in the first round alone, and the first moments by
the norm of their difference as well as by their norms: a difference counts
every coordinate's error, where a norm lets errors of either sign cancel.
(The update's difference is no such number: Adam's normalised steps on
coordinates with small gradients differ in sound runs nearly as much as in
the control.)
"""
from __future__ import annotations

from typing import Any, Dict

import jax

from bench.jobs.rounds import Job as RoundsJob
from bench.lib import compare


class Job(RoundsJob):
    def numbers(self, got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
        """``first_loss_gap``: the first round's mean loss, relative;
        ``moment_gap`` and ``update_gap``: as ``rounds`` (by the norms);
        ``moment_diff``: the first moments by the norm of their difference
        (``compare.diff_gap``), over leaves the reference moves."""
        m_got, m_want = jax.tree.leaves(got["m1"]), jax.tree.leaves(want["m1"])
        moving = compare.moving_leaves(m_want)
        d = self._gal_change
        return {
            "first_loss_gap": compare.rel_gap(got["loss"][0], want["loss"][0]),
            "moment_gap": compare.norm_gap(m_got, m_want, moving),
            "moment_diff": compare.diff_gap(*[[x for x, m in zip(leaves, moving) if m]
                                              for leaves in (m_got, m_want)]),
            "update_gap": compare.norm_gap(d(got["global"][-1]), d(want["global"][-1]), moving),
        }

    def detail(self, got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, Any]:
        """``rounds``' detail, and each checked round's mean loss, the
        program's and the reference's."""
        out = super().detail(got, want)
        out["loss_program"] = [float(x) for x in got["loss"]]
        out["loss_reference"] = [float(x) for x in want["loss"]]
        return out
