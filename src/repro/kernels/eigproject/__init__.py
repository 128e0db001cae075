from repro.kernels.eigproject.ops import project_norms, project_norms_table
