"""The training entry point with its timed path broken underneath: the
step computes its loss and then returns its state unchanged (only the
step counter moves). The harness test puts this in the place of
`-m runbooks_tpu.train.trainer` and must see `correct` come out false."""

import jax

import runbooks_tpu.train.trainer as trainer

_make = trainer.make_lora_train_step


def _broken(*args, **kwargs):
    step = _make(*args, **kwargs)

    def unchanged(state, base_params, batch):
        kept = jax.tree.map(lambda x: x.copy(), state)   # step donates
        new, metrics = step(state, base_params, batch)
        return type(new)(step=new.step, params=kept.params,
                         opt_state=kept.opt_state), metrics

    return unchanged


trainer.make_lora_train_step = _broken

raise SystemExit(trainer.main())
