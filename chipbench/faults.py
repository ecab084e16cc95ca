"""Faults planted in the program under the timed path, to show that the
output check sees them (chipbench/tests/fault_run.py on the host CPU,
chipbench/calibrate.py ``--plant`` on the chip). Call ``plant`` before
the cell builds its step.

  unchanged         the step returns the state it was given;
  half_batch        every server loss is a mean over the first half of
                    the rows;
  no_exchange       the data-parallel step never averages its losses
                    across chips (each chip updates from its own shard's
                    loss);
  answer            the loss h the step returns is altered by 1%;
  no_server_update  the server's update is dropped (w0 kept as it was,
                    as a server coefficient of 0 would leave it); the
                    parties' updates and every loss stay as they were.
"""
from __future__ import annotations

FAULTS = ("unchanged", "half_batch", "no_exchange", "answer",
          "no_server_update")


def plant(fault: str) -> None:
    from repro.core import asyrevel, exchange, vfl

    if fault in ("unchanged", "answer"):
        step = asyrevel.asyrevel_step

        def broken(model, vfl_cfg, state, batch, ex=None):
            new, h = step(model, vfl_cfg, state, batch, ex)
            return (state, h) if fault == "unchanged" else (new, h * 1.01)

        asyrevel.asyrevel_step = broken
    elif fault == "half_batch":
        tf_fwd = vfl.TransformerVFLModel.server_forward
        lr_fwd = vfl.PaperLRModel.server_forward

        def tf_half(self, w0, cs, batch):
            k = cs.shape[0] // 2
            return tf_fwd(self, w0, cs[:k],
                          {n: a[:k] for n, a in batch.items()})

        def lr_half(self, w0, cs, y):
            k = cs.shape[0] // 2
            return lr_fwd(self, w0, cs[:k], y[:k])

        vfl.TransformerVFLModel.server_forward = tf_half
        vfl.PaperLRModel.server_forward = lr_half
    elif fault == "no_exchange":
        asyrevel.PmeanVFLModel.server_forward = (
            lambda self, w0, cs, y: self.inner.server_forward(w0, cs, y))
    elif fault == "no_server_update":
        exchange.ZOExchange.server_update = (
            lambda self, w0, key, f_base, f_of, lr: w0)
    else:
        raise SystemExit(f"unknown fault {fault!r}; have {FAULTS}")
