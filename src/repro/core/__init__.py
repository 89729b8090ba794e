"""Core module — the paper's learning/inference engine (paper Table 1 'core').

Submodules:
  expfam               conjugate exponential-family algebra
  dag                  modeling language (Variables/DAG/BayesianNetwork/PlateSpec)
  vmp                  variational message passing (single device)
  dvmp                 distributed VMP (shard_map + psum)
  svi                  stochastic variational inference
  streaming            Bayesian updating (Eq. 3), streaming VB, concept drift
  importance_sampling  parallel likelihood weighting for CLG networks
  factored_frontier    dynamic-BN filtering/smoothing (lax.scan)
  map_inference        scalable MAP / abductive inference

Exact inference (junction tree) lives in the sibling package
``repro.infer_exact`` — the paper's HUGIN link, replaced natively.
"""
