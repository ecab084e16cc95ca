"""The paper's contribution: ZOO-VFL framework + AsyREVEL algorithms."""
from repro.core.zoo import (perturb, zo_coefficient, zo_gradient,  # noqa
                            direction_tree)
from repro.core.vfl import (VFLModel, PaperLRModel, PaperFCNModel,  # noqa
                            TransformerVFLModel)
