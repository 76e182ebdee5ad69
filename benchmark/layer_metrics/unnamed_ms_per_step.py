"""Device time a step of the operations that carry no name stack at all
(no `tf_op`: collectives and copies that GSPMD and the chip compiler add
without saying for whom, an instruction that lost its name in a pass):
part of `other_ms_per_step`, by class in `extras.step_classes.unnamed`.
The journal's `step_program.unnamed_instructions` lists what they are in
the compiled program."""

from benchmark.harness.trace import classes


def read(run):
    return classes.unnamed_ms(run)
