"""The train-side names a family gives. No cell of this family trains:
the model is 3.85 B parameters, and at 16 bytes a parameter (float32
master weights, gradients and Adam's two moments) it needs 61.6 GB where
one chip holds 16; a cut in depth would break it (the cross-decoder's
layers read the memory and the keys and values of the two layers before
it). Each name says so."""

NO_TRAINING = (
    "the sambay family has no training cell: Phi-4-mini-flash-reasoning "
    "is 3,852,562,944 parameters, 61.6 GB at 16 bytes a parameter, and "
    "one chip holds 16 GB; {name} is not implemented")


def train_loss(cfg):
    raise NotImplementedError(NO_TRAINING.format(name="train_loss"))


def param_specs(cfg):
    raise NotImplementedError(NO_TRAINING.format(name="param_specs"))
