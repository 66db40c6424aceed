"""Tier-1 runs the fast tests of the benchmark's ingress configuration
(PR 45).

``wafbench/tests/test_ingress.py`` pins ``crs-lite-pl2-ingress``: its rule
text against ``crs-lite-pl2``'s, its pool (the go-ftw requests under their
own table's verdicts, the synthetic ones as the tool makes them), every
steady burst and prime group as one ``512x512`` window inside one socket
read, its control, the two readers its cell brings, and ``freeze_ingress``
on a 32-request slice. The file is the benchmark's and stays where it is;
its fast tests are imported here so that every PR runs them. The one that
regenerates the whole data stays with ``pytest wafbench/tests``.
"""

from wafbench.tests.test_ingress import (  # noqa: F401
    host_engines,
    test_every_burst_and_prime_group_is_one_512x512_window_inside_one_read,
    test_freeze_ingress_is_deterministic_on_a_32_request_slice,
    test_matcher_device_us_per_row,
    test_row_padding_share,
    test_the_cell_resolves_and_states_its_deployment,
    test_the_control_lets_through_what_the_942_family_alone_blocked,
    test_the_pool_is_the_interactive_go_ftw_requests_and_770_synthetic_gets,
    test_the_rule_text_is_crs_lite_pl2s_byte_for_byte,
)
