"""The system under test, built from a configuration and a traffic mix.

The benchmark takes from the program only its serving layers: the
scenario's model, the spec, and a `SmootherServer`. ``backend`` and
``combine_impl`` stay at the program's defaults, so what the program
picks is what is measured.
"""
from __future__ import annotations

from repro.launch.autobatch import pad_width


def build_server(config: dict, traffic: dict):
    """A `SmootherServer` for the configuration's scenario and spec, at
    the traffic's launch width."""
    import jax.numpy as jnp

    from repro.launch.serve import SmootherServeConfig, SmootherServer
    from repro.scenarios import get_scenario

    scenario = get_scenario(config["program"]["scenario"])
    model = scenario.make_model(jnp.dtype(config["dtype"]))
    spec = scenario.default_spec(**config["spec"])
    serve_cfg = SmootherServeConfig(
        max_batch=traffic["max_batch"], f64=False,
        method=spec.method, n_iter=spec.n_iter, tol=spec.tol,
        lm_lambda=spec.lm_lambda,
        policy=traffic.get("policy", "static"),
        deadline_s=traffic.get("deadline_s", 2.0),
        max_wait_s=traffic.get("max_wait_s", 0.25),
        slack=traffic.get("slack", 1.25))
    return SmootherServer(model, serve_cfg, spec=spec)


def launch_shapes(server, lens) -> dict:
    """``n_pad -> {b_pad}``: the launches `serve_requests` makes for
    tracks of these lengths (one per ``max_batch`` chunk of a bucket)."""
    counts: dict = {}
    for n in lens:
        n_pad = server.queue_signature(int(n))[2]
        counts[n_pad] = counts.get(n_pad, 0) + 1
    max_batch = server.cfg.max_batch
    shapes: dict = {}
    for n_pad, count in counts.items():
        widths = shapes.setdefault(n_pad, set())
        for lo in range(0, count, max_batch):
            widths.add(pad_width(min(max_batch, count - lo), max_batch))
    return shapes


def backend_choices() -> dict:
    """The combine lowering the program chose per launch shape."""
    from repro.kernels.kalman_combine import autotune

    return {k: v["choice"] for k, v in autotune.cache_entries().items()}
