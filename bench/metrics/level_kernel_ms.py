"""Device milliseconds of the fused level kernel per request answered:
the summed durations of the trace's Pallas kernel operations (custom
calls to ``tpu_custom_call``; on the packed backend the only Pallas
kernel is ``frontier.packed_level_blocks``' level kernel, which carries
no name of its own in the trace) inside the traced window, over the
requests answered inside it."""

KERNELS = r"^custom-call tpu_custom_call "


def read(obs):
    done = obs.completed_in_window()
    if obs.trace is None or not done:
        return None
    s = obs.trace.kernel_s(KERNELS)
    if s <= 0:
        return None
    return 1e3 * s / len(done)
