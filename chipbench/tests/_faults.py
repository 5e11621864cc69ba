"""Faults planted in the timed path; each must make ``correct`` false."""
from __future__ import annotations

import dataclasses

import numpy as np


def stale_state(monkeypatch):
    """An observe that returns its state unchanged: after a pool's first
    fit, completions update neither its models nor its decision cache."""
    from repro.core.predictor import SizeyPredictor
    orig = SizeyPredictor._maybe_refit

    def stale(self, key, pool, seed):
        if key not in self.states:
            orig(self, key, pool, seed)
    monkeypatch.setattr(SizeyPredictor, "_maybe_refit", stale)


def _patch_decisions(monkeypatch, alter):
    from repro.core.predictor import SizeyPredictor
    orig = SizeyPredictor.predict_batch

    def patched(self, tasks):
        out = orig(self, tasks)
        model = [i for i, d in enumerate(out) if d.source == "model"]
        alter(out, model)
        return out
    monkeypatch.setattr(SizeyPredictor, "predict_batch", patched)


def half_batch(monkeypatch):
    """Half of each batch left out: the first half is decided, the rest
    get the mean allocation of the first half."""
    def alter(out, model):
        keep = model[:(len(model) + 1) // 2]
        for i in model[len(keep):]:
            mean = float(np.mean([out[j].allocation_gb for j in keep]))
            out[i] = dataclasses.replace(out[i], allocation_gb=mean)
    _patch_decisions(monkeypatch, alter)


def answer_altered(monkeypatch):
    """One answer of each batch altered where it is produced: the first
    decision's allocation 5% high, whether it came from the models or
    from the user's preset."""
    def alter(out, model):
        if out:
            out[0] = dataclasses.replace(
                out[0], allocation_gb=out[0].allocation_gb * 1.05)
    _patch_decisions(monkeypatch, alter)


def all_presets(monkeypatch):
    """The gate between the user's preset and the models broken: every
    decision falls back to the preset, as for a pool without history."""
    from repro.core.predictor import SizeyPredictor

    def alter(out, model):
        for i in model:
            d = out[i]
            out[i] = SizeyPredictor._preset_decision(
                d.task_type, d.machine, d.features, d.user_preset_gb,
                d.machine_cap_gb)
    _patch_decisions(monkeypatch, alter)


def mlp_scan_short(monkeypatch):
    """The MLP's Adam scan cut to half of its configured steps."""
    from chipbench.harness import serve
    orig = serve.sizey_config

    def short(method):
        cfg = orig(method)
        return dataclasses.replace(cfg,
                                   mlp_train_steps=cfg.mlp_train_steps // 2)
    monkeypatch.setattr(serve, "sizey_config", short)


FAULTS = {"stale_state": stale_state, "half_batch": half_batch,
          "answer_altered": answer_altered, "all_presets": all_presets,
          "mlp_scan_short": mlp_scan_short}
