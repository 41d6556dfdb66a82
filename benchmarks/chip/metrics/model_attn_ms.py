"""Device ms per step under the step's model.attn scopes (each layer's
attention with its input norm: forward, backward and recompute), self
time, the mean over the chips (`model_scopes.py`)."""
import model_scopes


def read(cell, trace, measured):
    return model_scopes.read(cell, trace, ("attn",))
