"""The train-side names a family gives. No cell of this family trains:
at 16 bytes a parameter (float32 master weights, gradients and Adam's
two moments) even the floors of the sizing guide (the dense layer and 4
expert layers of 8 experts, an eighth of the vocabulary: 1.85 B
parameters) need 29.6 GB, and one chip has 16. Each name says so."""

NO_TRAINING = (
    "the sarvam_mla family has no training cell: the dense layer and 4 "
    "expert layers of 8 experts with an eighth of the vocabulary are "
    "1.85 B parameters, 29.6 GB at 16 bytes a parameter, and one chip "
    "holds 16 GB; {name} is not implemented")


def train_loss(cfg):
    raise NotImplementedError(NO_TRAINING.format(name="train_loss"))


def param_specs(cfg):
    raise NotImplementedError(NO_TRAINING.format(name="param_specs"))
