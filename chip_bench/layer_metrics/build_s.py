"""Host build of the network: StepProgram(...) and init_state."""


def read(rec):
    return rec.setup["build_s"]
