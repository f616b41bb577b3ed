"""One module per published architecture family, named by the
configuration file's ``model_type``: ``bench/families/<model_type>.py``.
``bench/run.py`` ``family`` loads it by its path, so a new family is a new
file. A family module provides:

``sizes(cfg)``
    the sizes the program and the reference need, from the configuration
    file (a dict of the family's own keys; ``vocab``, the vocabulary's
    size, among them);
``layout(s)``
    the weight tree the served program takes, (shape, "norm" | "matrix")
    per leaf, for ``bench.weights.make``;
``model_config(name, cfg, s)``
    the program's ``ModelConfig``;
``Reference(params, s, two_t=None)``
    the float32 reference (a ``bench.reference.Reference``), with
    ``rows(...)`` and ``calibration(prompts)``: the stack of calibration
    activations, one block per layer the program's policy prepares, in
    the order it takes them;
``calibrate_2t(params, s, calib, *, p, importance, drop_target, delta)``
    the reference's 2T-Drop thresholds and major neurons from that stack;
``model_flops(s, steps, kept_share)``
    the model operations the traced steps need (``bench/work.py``);
``moe_needed(s, p, steps, kept_share)``
    the operations and bytes their MoE layers need.
"""
