"""Scenario registry: nonlinear SSM model zoo for the smoother service.

Importing this package registers the full catalogue (DESIGN.md §7,
EXPERIMENTS.md scenario table):

  * ``coordinated_turn``       — paper §5 turn-rate tracking (nx=5, ekf)
  * ``bearings_only``          — CV target, passive bearings (nx=4, ekf)
  * ``pendulum``               — sin(theta) observation (nx=2, slr)
  * ``lorenz96``               — chaotic ring, partial obs (nx=8, ekf)
  * ``lorenz96_40``            — the same ring at the data-assimilation
                                 standard, every site observed (nx=40, ekf)
  * ``stochastic_volatility``  — AR(1) log-vol, exp obs (nx=1, slr)
  * ``population``             — logistic growth, exp obs (nx=1, slr)

Usage:

    from repro.scenarios import get_scenario
    sc = get_scenario("pendulum")
    model = sc.make_model(jnp.float64)
    xs, ys = sc.simulate(model, 200, jax.random.PRNGKey(0))
    cfg = sc.default_config(n_iter=10, tol=1e-6)   # model_id baked in
"""
from .base import (Scenario, get_scenario, list_scenarios, register,
                   simulate_trajectory)
from . import (bearings_only, coordinated_turn, lorenz96, pendulum,
               population, stochastic_volatility)  # noqa: F401 (register)
from .coordinated_turn import (CoordinatedTurnConfig,
                               make_coordinated_turn_model)

__all__ = [
    "Scenario", "register", "get_scenario", "list_scenarios",
    "simulate_trajectory",
    "CoordinatedTurnConfig", "make_coordinated_turn_model",
]
