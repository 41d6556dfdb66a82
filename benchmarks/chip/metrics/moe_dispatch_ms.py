"""Device ms per step under the expert layers' model.moe.router and
model.moe.dispatch scopes (router scores, top-k and balance loss; bucket
positions, one-hots, the dispatch and combine einsums), self time, the
mean over the chips (`model_scopes.py`)."""
import model_scopes


def read(cell, trace, measured):
    return model_scopes.read(cell, trace, ("moe_router", "moe_dispatch"))
