"""compiles_in_window — programs lowered for compilation inside the window.

Count of JAX's ``/jax/core/compile/jaxpr_to_mlir_module_duration`` events
(one per program JAX had to lower, whether the persistent cache then held
it or not) between the window's open and close, heard by the harness's own
listener. Expected 0: every shape the cell uses is warmed in set-up.
"""

def read(view):
    return float(view.compiles_in_window)
