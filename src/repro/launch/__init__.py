from repro.launch import mesh
