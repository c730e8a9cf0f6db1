"""Device time per update of the backward flash kernel (one kernel since PR
53: dq, dk and dv from one score tile): the operations that carry the name
the program gives it (``relayrl_flash_bwd``: ``pallas_call`` name and
``named_scope``, ``ops/scopes.BWD_NAME``), summed over the update modules that
lie wholly inside the traced window, per such update. A windowed or latent
call's kernel (``relayrl_flash_bwd_win`` / ``_mla``) carries the name too and
is counted; ``flash_window_ms`` / ``flash_mla_ms`` tell those apart."""

from benchmark import program_trace


def read(run):
    return program_trace.kernel_ms_per_update(run, "relayrl_flash_bwd")
