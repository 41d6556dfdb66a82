"""Device ms per step under the expert layers' model.moe.experts and
model.moe.shared scopes (the held routed experts and the shared
experts), self time, the mean over the chips (`model_scopes.py`)."""
import model_scopes


def read(cell, trace, measured):
    return model_scopes.read(cell, trace, ("moe_experts", "moe_shared"))
