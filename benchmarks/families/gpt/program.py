"""The two names of the program that ``DistributedTrainStep`` needs for
a GPT-family model (moved here from ``lib/program.py``'s
``build_train_step``). Imported when called: the family's reference
imports nothing of the program."""


def train_loss(cfg):
    """The loss ``DistributedTrainStep`` differentiates: (params, batch)
    -> scalar."""
    from paddle_tpu.models import gpt_loss

    return lambda p, b: gpt_loss(cfg, p, b)


def param_specs(cfg):
    from paddle_tpu.models import gpt_param_specs

    return gpt_param_specs(cfg)
