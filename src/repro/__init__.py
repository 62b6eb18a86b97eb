"""repro — production-grade JAX reproduction of
"Canary: Congestion-Aware In-Network Allreduce Using Dynamic Trees"
(De Sensi et al., 2023), plus its TPU-native adaptation and a multi-arch
training/serving framework around it.
"""
__version__ = "1.0.0"
