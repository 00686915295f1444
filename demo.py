"""End-to-end demo — CLI parity with the reference demo (demo.py:62-77).

  python demo.py manager <host> <port> [--secure] [--cpu]
                 [--aggregator SPEC] [--cohort FRAC] [--quantize-broadcast BITS]
  python demo.py worker  <manager-host:port> <port> [--cpu]
                 [--compress SPEC]

Manager flags:
  --secure              Bonawitz double-masking secure aggregation
                        (server/secure.py): uploads are masked tensors the
                        manager cannot read individually.
  --aggregator SPEC     "mean" (default, reference semantics),
                        "median", or "trimmed:<ratio>" — Byzantine-robust.
  --cohort FRAC         FedAvg's C: sample this fraction of registered
                        clients per round instead of notifying everyone.
  --quantize-broadcast BITS
                        8 or 16: ship each round's weights stochastically
                        quantized (4x/2x smaller downlink).
Worker flags:
  --compress SPEC       "topk:<frac>[:q8|q16]": upload sparse round
                        deltas with error feedback instead of full
                        weights (ops/compression.py).
Either role:
  --cpu                 run this process on the host CPU. A chip belongs
                        to one process at a time, so on a TPU host the
                        manager (which only folds, on the host) takes
                        --cpu and each worker owns one chip.

Same shape as the reference: the manager hosts the "lineartest"
experiment (a 10→1 linear regressor); each worker invents
``32·randint(5,20)`` samples of ``y = p·X`` for the fixed coefficient
vector and trains locally with SGD lr=0.001, batch 32 (demo.py:29-59
semantics — but the local loop is one jitted XLA program here).

Drive it exactly like the reference:
  curl 'http://<host>:<port>/lineartest/start_round?n_epoch=8'
  curl 'http://<host>:<port>/lineartest/loss_history'
"""

import argparse


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="demo.py", usage=__doc__, add_help=False
    )
    p.add_argument("role", choices=["manager", "worker"])
    p.add_argument("host")  # worker quirk kept: this is the MANAGER address
    p.add_argument("port", type=int)
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--secure", action="store_true")
    p.add_argument("--aggregator", default="mean")
    p.add_argument("--cohort", type=float, default=1.0)
    p.add_argument("--quantize-broadcast", type=int, default=None,
                   choices=(8, 16), dest="quantize_broadcast")
    p.add_argument("--compress", default=None)
    return p


def main() -> None:
    parser = _build_parser()
    args = parser.parse_args()
    # validate flag VALUES up front so a typo prints the usage, not a
    # library traceback from deep inside Experiment/worker construction
    try:
        from baton_tpu.ops.aggregation import parse_aggregator
        from baton_tpu.server.http_worker import _parse_compress

        parse_aggregator(args.aggregator)
        _parse_compress(args.compress)
        if not (0.0 < args.cohort <= 1.0):
            raise ValueError(f"--cohort must be in (0, 1], got {args.cohort}")
        if args.secure and args.aggregator != "mean":
            raise ValueError(
                "--secure needs --aggregator mean (the server only sees "
                "the masked sum)"
            )
    except ValueError as e:
        parser.error(str(e))
    manager_only = {
        "--secure": args.secure,
        "--aggregator": args.aggregator != "mean",
        "--cohort": args.cohort != 1.0,
        "--quantize-broadcast": args.quantize_broadcast is not None,
    }
    if args.role == "worker" and any(manager_only.values()):
        # manager-side policies: a worker follows whatever the round
        # broadcast demands, so silently accepting these would mislead
        bad = [k for k, v in manager_only.items() if v]
        print(f"worker does not take {', '.join(bad)}\n{__doc__}")
        raise SystemExit(1)
    if args.role == "manager" and args.compress is not None:
        print(f"--compress is a worker flag\n{__doc__}")
        raise SystemExit(1)

    import jax

    from baton_tpu.utils.profiling import enable_compile_cache

    enable_compile_cache()
    if args.cpu:
        # must precede the first backend touch; jax.config outranks a
        # JAX_PLATFORMS the environment may carry
        jax.config.update("jax_platforms", "cpu")

    import numpy as np
    from aiohttp import web

    from baton_tpu.core.training import make_local_trainer
    from baton_tpu.data.synthetic import linear_client_data
    from baton_tpu.models.linear import linear_regression_model
    from baton_tpu.server.http_manager import Manager
    from baton_tpu.server.http_worker import ExperimentWorker

    model = linear_regression_model(10)  # name="lineartest"
    app = web.Application()

    if args.role == "manager":
        manager = Manager(app)
        manager.register_experiment(
            model,
            round_timeout=600.0,
            secure_agg=args.secure,
            aggregator=args.aggregator,
            cohort_fraction=args.cohort,
            broadcast_quantize_bits=args.quantize_broadcast,
        )
    else:
        nprng = np.random.default_rng()

        def get_data():
            data = linear_client_data(nprng)
            return data, data["x"].shape[0]

        import secrets as _secrets

        worker = ExperimentWorker(
            app,
            model,
            manager=args.host,  # reference quirk kept: worker's 2nd arg is the manager address
            port=args.port,
            trainer=make_local_trainer(model, batch_size=32, learning_rate=0.001),
            get_data=get_data,
            compress=args.compress,
            # unique per process: quantizer rounding noise must be
            # independent across workers or the cohort mean's error
            # stops shrinking with N (ops/compression.py seed note)
            rng_seed=_secrets.randbits(31),
        )
        # per-epoch progress at GET /{name}/metrics (user-supplied
        # trainers don't get the hook automatically; one worker per
        # process here, so a worker-unique trainer costs nothing)
        worker.enable_progress_metrics()

    web.run_app(app, port=args.port)


if __name__ == "__main__":
    main()
