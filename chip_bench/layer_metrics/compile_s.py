"""StepProgram.lower_run(...).compile() of the cell's run program."""


def read(rec):
    return rec.setup["compile_s"]
