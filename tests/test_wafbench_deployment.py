"""Tier-1 runs the fast deployment tests of the benchmark's harness.

``wafbench/tests/test_deployment.py`` pins what a configuration's
``instances`` and ``sidecar_args`` deploy (the argv and rule texts of the
cells that were there, the refusal of a flag the harness sets, the
control's one edited instance, the repeat generator's bytes). The file
is the benchmark's and stays where it is; its JAX-free tests are
imported here so that every PR runs them. Its two whole runs on the CPU
(each starts a sidecar) stay with ``pytest wafbench/tests``.
"""

from wafbench.tests.test_deployment import (  # noqa: F401
    test_a_flag_of_the_configurations_own_is_not_refused,
    test_a_flag_the_harness_sets_is_refused,
    test_a_plans_prime_groups_are_sent_in_place_of_the_whole_pool,
    test_control_edits_the_instance_it_names_and_no_other,
    test_existing_configurations_deploy_what_they_did,
    test_the_new_cells_resolve,
    test_the_repeat_cell_that_was_there_sends_the_bytes_it_sent,
    test_two_instances_and_sidecar_args_are_deployed_in_order,
)
