"""Quickstart: the penalized glmnet-parity API end-to-end, then the paper's
raw constrained form (Algorithm 1).

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp

from repro.baselines import elastic_net_cd
from repro.core import (ElasticNet, ElasticNetCV, SvenConfig, enet_path,
                        sven)
from repro.core.elastic_net import lambda1_max
from repro.data.synthetic import make_regression


def main():
    # A p >> n problem (the Elastic Net's home turf: genomics/fMRI shapes)
    X, y, beta_true = make_regression(n=60, p=500, k_true=8, rho=0.4, seed=0)

    # --- penalized API (what glmnet users write) ---------------------------
    lam2 = 1.0
    lam1 = 0.3 * float(lambda1_max(X, y))
    model = ElasticNet(lambda1=lam1, lambda2=lam2).fit(X, y)
    nnz = int((jnp.abs(model.coef_) > 1e-8).sum())
    print(f"ElasticNet(lambda1={lam1:.2f}): {nnz} / 500 features, "
          f"intercept={float(model.intercept_):.2e}, mapped to t={float(model.t_):.3f}")

    # parity with the coordinate-descent baseline (the glmnet stand-in)
    beta_cd = elastic_net_cd(X, y, lam1, lam2).beta
    res = ElasticNet(lam1, lam2, standardize=False, fit_intercept=False).fit(X, y)
    print(f"max |beta_sven - beta_cd| = {float(jnp.abs(res.coef_ - beta_cd).max()):.2e}")

    # full regularization path: ONE compiled scan over the glmnet grid,
    # gap-safe screening fused at every point
    path = enet_path(X, y, n_lambdas=20, lambda2=lam2)
    print(f"enet_path: {path.betas.shape[0]} lambdas, screened problem sizes "
          f"{int(path.n_kept.min())}..{int(path.n_kept.max())} of 500")

    # K-fold CV, all folds batched through one vmapped scan
    cv = ElasticNetCV(k=5, n_lambdas=20, lambda2=lam2).fit(X, y)
    print(f"ElasticNetCV: lambda_min={cv.lambda_min_:.3f} "
          f"(grid point {int(jnp.argmin(cv.mean_mse_))}/20), "
          f"cv_mse={float(cv.mean_mse_.min()):.4f}")

    # --- constrained API (the paper's Algorithm 1) -------------------------
    t = float(jnp.sum(jnp.abs(beta_cd)))
    sol = sven(X, y, t, lam2)   # auto-dispatches: 2p > n -> primal Newton-CG
    print(f"sven: mode={sol.mode}  newton_iters={int(sol.iters)}  "
          f"kkt_violation={float(sol.kkt):.2e}")

    # the same solve through the Pallas kernel backend (interpret mode on CPU)
    sol_k = sven(X, y, t, lam2, SvenConfig(backend="auto", tol=1e-6))
    print(f"pallas backend agreement: {float(jnp.abs(sol_k.beta - sol.beta).max()):.2e}")


if __name__ == "__main__":
    main()
