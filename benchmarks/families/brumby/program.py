"""The train-side names a family gives. No cell of this family trains:
at 16 bytes a parameter (float32 master weights, gradients and Adam's
two moments) even the floors of the sizing guide (5 layers of 330.35 M
parameters and an eighth of the vocabulary, 194.5 M in embedding and
head) need 29.5 GB, and one chip has 16. Each name says so."""

NO_TRAINING = (
    "the brumby family has no training cell: 5 layers of 330.35 M "
    "parameters with an eighth of the vocabulary (194.5 M) are 1.85 B "
    "parameters, 29.5 GB at 16 bytes a parameter, and one chip holds "
    "16 GB; {name} is not implemented")


def train_loss(cfg):
    raise NotImplementedError(NO_TRAINING.format(name="train_loss"))


def param_specs(cfg):
    raise NotImplementedError(NO_TRAINING.format(name="param_specs"))
