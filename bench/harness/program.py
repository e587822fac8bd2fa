"""The system under test, built from a configuration file.

The program's own entry points build what the cells run:
``launch/train.py``'s argument parser and ``build_run`` for a training
job (the RunConfig a ``train.py`` command line would train with), and
``ServeEngine`` for serving.  The configuration file states the model as
published; the only changes made to the program's preset are the ones
the file states (its cut depth, its norm epsilon), and every width is
checked against the file before anything runs.
"""

from __future__ import annotations

import dataclasses

#: configuration-file key -> the program's ModelConfig field
_WIDTHS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "vocab_size": "vocab_size",
           "tie_word_embeddings": "tie_embeddings", "rope_theta": "rope_theta"}


class ConfigMismatch(RuntimeError):
    """The program's model differs from what the configuration states."""


def model_config(cfg: dict, base):
    """The program's ModelConfig ``base`` with the file's depth and norm
    epsilon, checked against every width the file states."""
    model = dataclasses.replace(base, num_layers=cfg["num_hidden_layers"],
                                norm_eps=cfg["rms_norm_eps"])
    for key, field in _WIDTHS.items():
        want, got = cfg[key], getattr(model, field)
        if want != got:
            raise ConfigMismatch(f"{key}: configuration {want}, program "
                                 f"{field}={got}")
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    if model.resolved_head_dim != hd:
        raise ConfigMismatch(f"head_dim {hd} != {model.resolved_head_dim}")
    return model


def train_run(cfg: dict, job: dict, *, interpret: bool = False,
              smoke_model=None):
    """The RunConfig ``launch/train.py`` builds for this job."""
    from repro.launch import train as driver
    lrd, opt = cfg["lrd"], cfg["optimizer"]
    argv = ["--arch", cfg["program_arch"],
            "--global-batch", str(job["global_batch"]),
            "--seq-len", str(job["seq_len"]), "--remat", job["remat"],
            "--optimizer", opt["name"], "--lr", str(opt["lr"]),
            "--warmup", str(opt["warmup_steps"]),
            "--steps", str(opt["total_steps"])]
    if lrd["enabled"]:
        argv += ["--lrd", "--alpha", str(lrd["alpha"]),
                 "--freeze", lrd["freeze"]]
        if not lrd["rank_quantize"]:
            argv.append("--no-rank-opt")
        if lrd["fused_kernels"]:
            argv.append("--use-pallas")
        if "min_dim" in lrd:
            argv += ["--lrd-min-dim", str(lrd["min_dim"])]
    if interpret:
        argv.append("--pallas-interpret")
    run = driver.build_run(driver.build_parser().parse_args(argv))
    model = smoke_model if smoke_model is not None else model_config(
        cfg, run.model)
    optim = dataclasses.replace(run.optim, momentum=opt["momentum"],
                                weight_decay=opt["weight_decay"])
    return dataclasses.replace(run, model=model, optim=optim)


def abstract_params(run):
    """Shapes and dtypes of the program's param tree for ``run``."""
    import jax
    from repro.launch import steps
    return jax.eval_shape(lambda: steps.init_params(run)[0])


def fallback_total() -> float:
    """Sum of the program's ``kernel_fallbacks{op, reason}`` counter."""
    from repro.obs import default_registry
    snap = default_registry().snapshot().get("kernel_fallbacks", {})
    return float(sum(snap.get("series", {}).values()))
