"""repro — systems reproduction of "System Support for Environmentally
Sustainable Computing in Data Centers" (FRAC storage codec, carbon-aware
training, ESE estimator, Amoeba engines) on jax/Pallas."""
