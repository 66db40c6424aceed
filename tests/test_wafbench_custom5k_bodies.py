"""Tier-1 runs the fast tests of the benchmark's feed-in-front-of-an-API
configuration (PR 43).

``wafbench/tests/test_custom5k_bodies.py`` pins
``crs-lite-pl2-custom5k-bodies``: its rule text against
``crs-lite-pl2-custom5k``'s, its pool by content type, template and
carrier, every steady burst as one ``32x2048`` window, its control, the
two readers its cell brings. The file is the benchmark's and stays where
it is; its fast tests are imported here so that every PR runs them. The
one that regenerates the data (an engine on 5,269 rules and four passes of
the host evaluator) stays with ``pytest wafbench/tests``.
"""

from wafbench.tests.test_custom5k_bodies import (  # noqa: F401
    test_every_burst_is_one_32x2048_window_and_a_pass_sends_every_custom_request_once,
    test_seg_conv_steps_per_launch,
    test_seg_long_scan_launch_share,
    test_the_cell_resolves_and_states_its_deployment,
    test_the_control_differs_on_exactly_the_24_a_feed_rule_decides,
    test_the_pool_is_the_bodied_pool_and_48_custom_bodied_requests,
    test_the_rule_text_is_custom5ks_byte_for_byte,
)
