"""Compiled matcher model families.

``waf_model`` is the flagship: the full Seclang ruleset lowered to a jittable
pytree (DFA banks + link/rule metadata + anomaly-score counters).
``eval_waf`` is the plain statement of one batch's math (``match_tier``
then ``post_match``) and what ``__graft_entry__`` lowers; the engine
serves the same math as ``match_tier_packed`` per tier plus one
``eval_post_tiered``.
"""

from .waf_model import WafModel, build_model, eval_waf  # noqa: F401
